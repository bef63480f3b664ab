"""The expert layer's two forms (ops/held_experts.py ``held_experts``): the
tile loop, and the grouped product whose three matmuls are the Pallas kernel
``expert_gmm`` (ops/pallas/expert_gmm.py), here in interpret mode as
tests/test_paged_attention.py runs ``paged_decode``.  Each against the plain
float32 sum over experts, with what the call counted."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import ServingEngine, serving
from paddle_tpu.models import pangu_moe
from paddle_tpu.ops import held_experts as he
from paddle_tpu.ops.pallas import expert_gmm as gmm_module

import programs

FORMS = ["loop", "grouped"]
COUNTS = ("experts_touched", "expert_tile_rows", "expert_tile_rows_live",
          "expert_rows_grouped")
TILE = 8        # rows of a tile, both forms: small enough that the cases cross it
HIGHEST = jax.lax.Precision.HIGHEST


def _weights(rng, n_held, E, F, dtype=jnp.float32):
    eg, eu = (jnp.asarray(rng.normal(size=(n_held, E, F)) * E ** -0.5, dtype)
              for _ in range(2))
    return eg, eu, jnp.asarray(rng.normal(size=(n_held, F, E)) * F ** -0.5, dtype)


def _plain(x, idx, w, eg, eu, ed, lo, valid=None):
    """sum over held experts e of (the weight a token gave e) * SwiGLU_e(x),
    in float32 whatever the operands' type."""
    x, eg, eu, ed = (np.asarray(a, np.float32) for a in (x, eg, eu, ed))
    idx, w = np.asarray(idx), np.asarray(w, np.float32)
    out = np.zeros(x.shape, np.float32)
    for e in range(eg.shape[0]):
        g = x @ eg[e]
        y = (g / (1.0 + np.exp(-g)) * (x @ eu[e])) @ ed[e]
        share = np.where(idx == lo + e, w, 0.0).sum(-1)
        if valid is not None:
            share = share * np.asarray(valid)
        out += share[:, None] * y
    return out


@pytest.fixture
def run(monkeypatch):
    """run(form, ...) -> (y, picks, counts): ``held_experts`` taking ``form``.
    The grouped form is steered as a test steers ``paged_decode``: the
    platform answers yes, the kernel runs in interpret mode, and the row tile
    is this file's; a float32 call is admitted by the test alone."""
    def run(form, x, idx, w, eg, eu, ed, lo, valid=None):
        counts = {n: jnp.zeros((), jnp.int32) for n in COUNTS}
        with monkeypatch.context() as m:
            if form == "grouped":
                m.setattr(he, "on_tpu", lambda: True)
                m.setattr(he, "expert_gmm",
                          functools.partial(gmm_module.expert_gmm, interpret=True))
                m.setattr(he, "_ROW_TILE", TILE if x.dtype == jnp.float32 else 16)
                m.setattr(he, "grouped_experts", functools.partial(
                    he.grouped_experts, row_tile=he._ROW_TILE))
                if x.dtype == jnp.float32:
                    m.setattr(he, "groups_in_kernel", lambda *a, **k: True)
            y, picks = he.held_experts(x, idx, w, eg, eu, ed, lo, valid,
                                              tile=TILE, counts=counts)
        return np.asarray(y), int(picks), {n: int(v) for n, v in counts.items()}
    return run


def _tile_rows(sizes, tile=TILE):
    return sum(-(-s // tile) * tile for s in sizes)


def _check_counts(form, counts, sizes, tile=TILE):
    picks = sum(sizes)
    assert counts == {"experts_touched": sum(s > 0 for s in sizes),
                      "expert_tile_rows": _tile_rows(sizes, tile),
                      "expert_tile_rows_live": picks,
                      "expert_rows_grouped": picks if form == "grouped" else 0}


@pytest.mark.parametrize("form", FORMS)
def test_even_routing(run, form):
    """48 tokens, 2 picks each, dealt round over 4 held experts: 24 rows an
    expert, three whole tiles each."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(48, 32)), jnp.float32)
    eg, eu, ed = _weights(rng, 4, 32, 16)
    t = np.arange(48)
    idx = jnp.asarray(np.stack([t % 4, (t + 1) % 4], -1), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(48, 2)), jnp.float32)
    y, picks, counts = run(form, x, idx, w, eg, eu, ed, 0)
    assert picks == 96 and np.abs(y - _plain(x, idx, w, eg, eu, ed, 0)).max() < 2e-5
    _check_counts(form, counts, [24] * 4)


@pytest.mark.parametrize("form", FORMS)
def test_no_pick_is_dropped_when_every_token_picks_one_expert(run, form):
    """No capacity: 40 tokens that all pick the same held expert all get it;
    and where no pick falls on a held expert the result is zeros, nothing was
    multiplied and no expert was read."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    eg, eu, ed = _weights(rng, 2, 16, 8)
    idx = jnp.full((40, 1), 5, jnp.int32)
    w = jnp.full((40, 1), 0.5, jnp.float32)
    y, picks, counts = run(form, x, idx, w, eg, eu, ed, 4)
    want = 0.5 * np.asarray(he._swiglu(x, eg[1], eu[1], ed[1]))
    assert picks == 40 and np.abs(y - want).max() < 1e-5
    _check_counts(form, counts, [0, 40])
    none, picks, counts = run(form, x, idx, w, eg, eu, ed, 8)
    assert picks == 0 and not none.any()
    _check_counts(form, counts, [0, 0])


@pytest.mark.parametrize("form", FORMS)
def test_valid_masks_rows(run, form):
    """A row the launch does not feed (``valid`` false) adds no pick."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(30, 32)), jnp.float32)
    eg, eu, ed = _weights(rng, 4, 32, 16)
    idx, w = pangu_moe.route(x, jnp.asarray(rng.normal(size=(32, 4)), jnp.float32), 2, 1.0)
    valid = jnp.arange(30) % 3 != 1
    y, picks, counts = run(form, x, idx, w, eg, eu, ed, 0, valid)
    assert picks == 2 * 20 == counts["expert_tile_rows_live"]
    assert np.abs(y - _plain(x, idx, w, eg, eu, ed, 0, valid)).max() < 2e-5
    assert not y[~np.asarray(valid)].any()
    assert counts["expert_rows_grouped"] == (picks if form == "grouped" else 0)


@pytest.mark.parametrize("form", FORMS)
def test_an_expert_across_row_tiles_and_a_row_tile_of_three_experts(run, form):
    """Sorted, the first eight picks are of three experts (3, 2 and 4 rows) and
    the fourth expert's 20 rows cross two tile boundaries; an expert in the
    middle of the range has none."""
    sizes = [3, 2, 0, 4, 20]
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(29, 32)), jnp.float32)
    eg, eu, ed = _weights(rng, 5, 32, 16)
    picks_of = rng.permutation(np.repeat(np.arange(5), sizes))
    idx = jnp.asarray(picks_of[:, None] + 2, jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(29, 1)), jnp.float32)
    y, picks, counts = run(form, x, idx, w, eg, eu, ed, 2)
    assert picks == 29 and np.abs(y - _plain(x, idx, w, eg, eu, ed, 2)).max() < 2e-5
    _check_counts(form, counts, sizes)


@pytest.mark.parametrize("form", FORMS)
def test_the_shares_of_four_chips_add_up(run, form):
    """``lo > 0`` with 4 of 16 experts held: the four shares' parts add up to
    the whole layer's routed part, every pick on exactly one share."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(50, 32)), jnp.float32)
    eg, eu, ed = _weights(rng, 16, 32, 16)
    idx, w = pangu_moe.route(x, jnp.asarray(rng.normal(size=(32, 16)), jnp.float32), 4, 2.5)
    parts, total = [], 0
    for lo in (0, 4, 8, 12):
        y, picks, counts = run(form, x, idx, w, eg[lo:lo + 4], eu[lo:lo + 4],
                               ed[lo:lo + 4], lo)
        sizes = [int(np.sum(np.asarray(idx) == lo + e)) for e in range(4)]
        _check_counts(form, counts, sizes)
        parts.append(y)
        total += picks
    assert total == 50 * 4
    assert np.abs(sum(parts) - _plain(x, idx, w, eg, eu, ed, 0)).max() < 2e-5


@pytest.mark.parametrize("form", FORMS)
def test_bfloat16_operands_stay_within_their_tolerance(run, form):
    """bf16 rows and matrices (the call ``groups_in_kernel`` itself admits: E
    and F whole lane tiles): float32 accumulation, so the result is within
    bf16's rounding of the float32 sum over the same bf16 values."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(40, 128)), jnp.bfloat16)
    eg, eu, ed = _weights(rng, 4, 128, 128, jnp.bfloat16)
    idx, w = pangu_moe.route(x, jnp.asarray(rng.normal(size=(128, 8)), jnp.bfloat16), 2, 1.0)
    y, picks, counts = run(form, x, idx, w, eg, eu, ed, 2)
    want = _plain(x, idx, w, eg, eu, ed, 2)
    assert np.abs(y - want).max() < 0.03 * np.abs(want).max()
    assert counts["expert_rows_grouped"] == (picks if form == "grouped" else 0)
    assert counts["expert_tile_rows_live"] == picks > 0


def test_admission_is_decided_from_what_the_call_shows(monkeypatch):
    ask = functools.partial(he.groups_in_kernel, hidden=2048, width=1536, rows=4096)
    assert not ask(jnp.bfloat16, jnp.bfloat16)                  # the CPU
    monkeypatch.setattr(he, "on_tpu", lambda: True)
    assert ask(jnp.bfloat16, jnp.bfloat16)
    assert not ask(jnp.float32, jnp.float32) and not ask(jnp.bfloat16, jnp.float32)
    assert not he.groups_in_kernel(jnp.bfloat16, jnp.bfloat16, hidden=64, width=32,
                                          rows=256)             # the tiny geometries
    assert not he.groups_in_kernel(jnp.bfloat16, jnp.bfloat16, hidden=2048,
                                          width=1536, rows=1 << 20)
    # a chunk is the tiles that hold the picks whatever the routing, or 16 MiB of rows
    assert he._chunk_tiles(2048, 64, 4096, 32, 16 << 20) == (128, 128)
    assert he._chunk_tiles(4096, 16, 15360, 32, 16 << 20) == (144, 34)


# ------------------------------------------------------------- the kernel
def _tiles(rng, tiles, tm, K, N, n_held, dtype=jnp.float32):
    x = jnp.asarray(rng.normal(size=(tiles * tm, K)), dtype)
    w = jnp.asarray(rng.normal(size=(n_held, K, N)) * K ** -0.5, dtype)
    te = jnp.asarray(np.sort(rng.integers(0, n_held, tiles)), jnp.int32)
    return x, w, te


@pytest.mark.parametrize("columns", [None, 128])
def test_the_kernel_multiplies_each_tile_by_its_expert(columns):
    rng = np.random.default_rng(7)
    x, w, te = _tiles(rng, 6, 8, 64, 256, 3)
    got = np.asarray(gmm_module.expert_gmm(x, w, te, jnp.int32(4), row_tile=8,
                                           columns=columns, interpret=True))
    want = jnp.einsum("tmk,tkn->tmn", x.reshape(6, 8, 64), w[te], precision=HIGHEST)
    # the four tiles in use; the two past them were not written
    assert np.abs(got[:32] - np.asarray(want).reshape(-1, 256)[:32]).max() < 2e-5


@pytest.mark.parametrize("n_tiles", [0, 3, 5])
def test_the_kernel_adds_weighted_rows_to_their_tokens(n_tiles):
    rng = np.random.default_rng(8)
    x, w, te = _tiles(rng, 5, 8, 64, 128, 4)
    token = jnp.asarray(rng.integers(0, 12, 40), jnp.int32)
    weight = jnp.asarray(rng.uniform(0, 1, 40) * (rng.uniform(size=40) < 0.7), jnp.float32)
    got = np.asarray(gmm_module.expert_gmm(x, w, te, jnp.int32(n_tiles), row_tile=8,
                                           combine=(token, weight, 12), interpret=True))
    y = np.asarray(jnp.einsum("tmk,tkn->tmn", x.reshape(5, 8, 64), w[te],
                              precision=HIGHEST)).reshape(40, 128)
    want = np.zeros((12, 128), np.float32)
    for r in range(n_tiles * 8):
        want[int(token[r])] += float(weight[r]) * y[r]
    assert got.shape == (12, 128) and np.abs(got - want).max() < 2e-5


def test_column_blocks_are_whole_lane_tiles_that_divide_the_width():
    assert gmm_module.column_block(2048, 1536, 2) == 768
    assert gmm_module.column_block(1536, 2048, 2) == 1024
    assert gmm_module.column_block(7680, 2048, 2) == 256
    assert gmm_module.column_block(2048, 7680, 2) == 768
    assert gmm_module.column_block(64, 128, 4) == 128
    assert gmm_module.column_block(64, 32, 4) == 32             # no whole tile: all of it
    # a combining call also keeps [tokens, columns] float32 in VMEM
    assert gmm_module.column_block(2048, 7680, 2, tokens=512) == 768
    assert gmm_module.column_block(1536, 2048, 2, tokens=4096) == 256


# ------------------------------------------------------------- the engine
def test_an_engine_steered_onto_the_chip_groups_every_pick(monkeypatch):
    """A bf16 LFM2 whose widths are whole lane tiles is a call the kernel
    admits. With ``on_tpu`` answering yes (the kernel in interpret mode) every
    pick on a held expert goes through the grouped product:
    ``expert_rows_grouped == moe_local_picks``, on the harvest spans and in
    ``state_summary()``; the unsteered engine counts the same picks and 0."""
    cfg = dict(programs.TINY["lfm2"], hidden_size=128, intermediate_size=128,
               moe_intermediate_size=128, num_hidden_layers=3,
               layer_types=programs.LFM2_TYPES[:3], num_dense_layers=1,
               torch_dtype="bfloat16")
    model, _ = programs.build("lfm2", cfg)
    prompts = programs.prompts([11, 5])

    def served():
        eng = ServingEngine(model, **programs.ENGINE)
        harvests = programs.harvests(eng)
        for p in prompts:
            eng.add_request(p, max_new_tokens=5)
        eng.run()
        return eng, [h[-1] for h in harvests]

    plain, seen = served()
    assert plain.moe_local_picks > 0 == plain.expert_rows_grouped
    assert all(a["expert_rows_grouped"] == 0 for a in seen)
    monkeypatch.setattr(serving, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(he, "on_tpu", lambda: True)
    monkeypatch.setattr(he, "expert_gmm",
                        functools.partial(gmm_module.expert_gmm, interpret=True))
    he._held_experts.clear_cache()
    try:
        eng, seen = served()
    finally:
        he._held_experts.clear_cache()
    assert eng.moe_tokens == plain.moe_tokens
    assert eng.expert_rows_grouped == eng.moe_local_picks == eng.expert_tile_rows_live > 0
    assert sum(a["expert_rows_grouped"] for a in seen) == eng.expert_rows_grouped
    assert eng.expert_tile_rows % he._ROW_TILE == 0
    assert eng.state_summary()["moe"] == {"tokens": eng.moe_tokens,
                                          "local_picks": eng.moe_local_picks,
                                          "rows_grouped": eng.expert_rows_grouped}
