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
COUNTS = ("experts_touched", "expert_tiles", "expert_tile_rows", "expert_tile_rows_live",
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
    is the ONE this file leaves the layout's rule to choose (or ``row_tiles``);
    a float32 call is admitted by the test alone."""
    def run(form, x, idx, w, eg, eu, ed, lo, valid=None, routed=None, row_tiles=None):
        counts = {n: jnp.zeros((), jnp.int32) for n in COUNTS}
        with monkeypatch.context() as m:
            if form == "grouped":
                m.setattr(he, "on_tpu", lambda: True)
                m.setattr(he, "expert_gmm",
                          functools.partial(gmm_module.expert_gmm, interpret=True))
                m.setattr(he, "_ROW_TILES",
                          row_tiles or (TILE if x.dtype == jnp.float32 else 16,))
                if x.dtype == jnp.float32:
                    m.setattr(he, "groups_in_kernel", lambda *a, **k: True)
            y, picks = he.held_experts(x, idx, w, eg, eu, ed, lo, valid,
                                       tile=TILE, counts=counts, routed=routed)
        return np.asarray(y), int(picks), {n: int(v) for n, v in counts.items()}
    return run


def _tile_rows(sizes, tile=TILE):
    return sum(-(-s // tile) * tile for s in sizes)


def _check_counts(form, counts, sizes, tile=TILE):
    picks = sum(sizes)
    assert counts == {"experts_touched": sum(s > 0 for s in sizes),
                      "expert_tiles": _tile_rows(sizes, tile) // tile,
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


# (tokens, k, held, routed, bytes of a row) of the four cells that run the layer: their
# mixed scans' 512 packed tokens -> (rows of a tile, the bound in tiles, tiles of a chunk)
CELLS = {
    "smallthinker21b.serve.mixed-length": ((512, 6, 64, 64, 2 * 2560), (64, 112, 72)),
    "lfm2-24b.serve.chat-batch": ((512, 4, 64, 64, 2 * 2048), (32, 128, 128)),
    "openpangu718b.serve.doc-batch": ((512, 8, 16, 256, 2 * 7680), (32, 144, 34)),
    "deepseekv32.serve.longdoc-batch": ((512, 8, 16, 256, 2 * 7168), (32, 144, 36)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_layout_follows_the_calls_geometry(cell):
    """The rule at the four cells: an expert's expected rows (tokens x k /
    routed: 48, 32, 16, 16) are ONE tile, the least of 32 / 64 / 128 that holds
    them; the chunk holds every tile even routing puts in use and eight more,
    so the loop over chunks does not run again at any of them (mixed-length's
    44-48 rows an expert were two tiles of 32, and two trips)."""
    (tokens, k, held, routed, row_bytes), want = CELLS[cell]
    row_tile, bound, chunk = he.layout(tokens, k, held, routed, row_bytes)
    assert (row_tile, bound, chunk) == want
    rows = -(-tokens * k // routed)
    assert rows <= row_tile and (row_tile == 32 or rows > row_tile // 2)
    in_use = held * -(-rows // row_tile)            # under even routing: one an expert
    assert in_use == held and in_use + he._SPARE_TILES <= chunk <= bound
    assert row_tile * chunk <= he._ROW_WORDS


@pytest.mark.parametrize("tokens,k,held,routed,want", [
    (48, 6, 64, 64, (32, 73, 73)),          # mixed-length's decode scan: the bound, no loop
    (128, 4, 64, 64, (32, 80, 80)),         # chat-batch's
    (64, 8, 16, 256, (32, 32, 32)),         # doc-batch's
    (4096, 8, 64, 64, (128, 320, 264)),     # past the largest tile: four tiles an expert
    (512, 8, 256, 256, (32, 384, 264)),     # every one of 256 held
])
def test_the_layout_at_other_shapes(tokens, k, held, routed, want):
    assert he.layout(tokens, k, held, routed, 2 * 2048) == want


# ------------------------------------------------------------- the kernel
def _tiles(rng, tiles, tm, K, N, n_held, dtype=jnp.float32):
    x = jnp.asarray(rng.normal(size=(tiles * tm, K)), dtype)
    w = jnp.asarray(rng.normal(size=(n_held, K, N)) * K ** -0.5, dtype)
    te = jnp.asarray(np.sort(rng.integers(0, n_held, tiles)), jnp.int32)
    return x, w, te


ROW_TILES = [8, 32, 64]     # this file's small one, and the layout's two that cells run


@pytest.mark.parametrize("tm", ROW_TILES)
@pytest.mark.parametrize("columns", [None, 128])
def test_the_kernel_multiplies_each_tile_by_its_expert(columns, tm):
    rng = np.random.default_rng(7)
    x, w, te = _tiles(rng, 6, tm, 64, 256, 3)
    got = np.asarray(gmm_module.expert_gmm(x, w, te, jnp.int32(4), row_tile=tm,
                                           columns=columns, interpret=True))
    want = jnp.einsum("tmk,tkn->tmn", x.reshape(6, tm, 64), w[te], precision=HIGHEST)
    # the four tiles in use; the two past them were not written
    assert np.abs(got[:4 * tm] - np.asarray(want).reshape(-1, 256)[:4 * tm]).max() < 2e-5


@pytest.mark.parametrize("tm", ROW_TILES)
@pytest.mark.parametrize("n_tiles", [0, 3, 5])
def test_the_kernel_adds_weighted_rows_to_their_tokens(n_tiles, tm):
    """The combining call: a spare row (weight 0, any token in range) adds
    nothing, a tile after the last one in use is not read, and with none in
    use the result is zeros."""
    rng = np.random.default_rng(8)
    rows = 5 * tm
    x, w, te = _tiles(rng, 5, tm, 64, 128, 4)
    token = jnp.asarray(rng.integers(0, 12, rows), jnp.int32)
    weight = jnp.asarray(rng.uniform(0, 1, rows) * (rng.uniform(size=rows) < 0.7), jnp.float32)
    got = np.asarray(gmm_module.expert_gmm(x, w, te, jnp.int32(n_tiles), row_tile=tm,
                                           combine=(token, weight, 12), interpret=True))
    y = np.asarray(jnp.einsum("tmk,tkn->tmn", x.reshape(5, tm, 64), w[te],
                              precision=HIGHEST)).reshape(rows, 128)
    want = np.zeros((12, 128), np.float32)
    for r in range(n_tiles * tm):
        want[int(token[r])] += float(weight[r]) * y[r]
    assert got.shape == (12, 128) and np.abs(got - want).max() < 5e-5


# ------------------------------------------------- the grouped form's layout
INTERPRETED = functools.partial(gmm_module.expert_gmm, interpret=True)


def _picks_of(rng, sizes, lo=0):
    """One pick a token, ``sizes[e]`` tokens on held expert ``e``, shuffled."""
    picks_of = rng.permutation(np.repeat(np.arange(len(sizes)), sizes))
    n = len(picks_of)
    return (jnp.asarray(picks_of[:, None] + lo, jnp.int32),
            jnp.asarray(rng.uniform(0.1, 1.0, size=(n, 1)), jnp.float32))


@pytest.mark.parametrize("chunk", [None, 4])
@pytest.mark.parametrize("row_tile", [32, 64])
def test_grouped_rows_at_the_layouts_row_tiles(row_tile, chunk):
    """``grouped_experts`` at the two row tiles the cells run, a chunk that
    holds the bound (no loop) and one of four tiles (two or three trips, the
    last with tiles after the last live one): experts of 70 and 150 rows lie
    across tiles at both, one has no row, two share nothing of their tile with
    another (their spare rows add nothing)."""
    sizes = [3, 70, 0, 2, 150]
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(sum(sizes), 32)), jnp.float32)
    eg, eu, ed = _weights(rng, 5, 32, 16)
    idx, w = _picks_of(rng, sizes)
    in_use = sum(-(-s // row_tile) for s in sizes)
    bound = he._bound(sum(sizes), 5, row_tile)
    assert in_use <= bound and (chunk is None or chunk < in_use)
    y, picks, touched, tiles, rows = he._held_experts(
        x, idx, w, eg, eu, ed, None, lo=0, tile=TILE, gmm=INTERPRETED,
        grouping=(row_tile, chunk or bound))
    assert np.abs(np.asarray(y) - _plain(x, idx, w, eg, eu, ed, 0)).max() < 5e-5
    assert (int(picks), int(touched), int(tiles), int(rows)) == (
        sum(sizes), 4, in_use, in_use * row_tile)


def test_picks_that_pile_up_run_the_chunk_loop_again_and_none_is_dropped(run, monkeypatch):
    """4 of 16 routed experts held and 40 tokens that ALL pick one of them: a
    chunk by expectation is four tiles (an expert expects 3 rows, one tile of
    8), the picks are five, so the loop over chunks makes a second trip, and
    every pick gets its expert."""
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    eg, eu, ed = _weights(rng, 4, 16, 8)
    idx = jnp.full((40, 1), 9, jnp.int32)
    w = jnp.full((40, 1), 0.5, jnp.float32)
    # a chunk by expectation at this tiny size: no spare tiles, no floor of bytes under it
    for name, value in (("_ROW_TILES", (TILE,)), ("_SPARE_TILES", 0), ("_CHUNK_BYTES", 0)):
        monkeypatch.setattr(he, name, value)
    assert he.layout(40, 1, 4, 16, 64) == (TILE, 9, 4)
    y, picks, counts = run("grouped", x, idx, w, eg, eu, ed, 8, routed=16)
    want = 0.5 * np.asarray(he._swiglu(x, eg[1], eu[1], ed[1]))
    assert picks == 40 and np.abs(y - want).max() < 1e-5
    _check_counts("grouped", counts, [0, 40, 0, 0])
    assert counts["expert_tiles"] == 5 > 4


@pytest.mark.parametrize("form", FORMS)
def test_an_expert_is_one_tile_at_mixed_lengths_routing(run, form):
    """mixed-length's routing, 470 live tokens of 512 with 6 picks each over 64
    held experts by a seeded router: 44 rows an expert (the fullest 60), which
    the layout's tile of 64 holds whole: ``expert_tiles / experts_touched`` is
    1, where tiles of 32 made it 2.  (The tile loop's tile is the caller's,
    this file's 8.)"""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(512, 32)), jnp.float32)
    eg, eu, ed = _weights(rng, 64, 32, 16)
    idx, w = pangu_moe.route_chosen(jnp.asarray(rng.normal(size=(512, 64)), jnp.float32), 6)
    valid = jnp.arange(512) < 470
    sizes = [int(np.sum(np.asarray(idx)[:470] == e)) for e in range(64)]
    assert 32 <= min(sizes) and max(sizes) <= 64 and sum(sizes) == 6 * 470
    assert he.layout(512, 6, 64, 64, 4 * 32)[0] == 64
    with jax.default_matmul_precision("highest"):
        y, picks, counts = run(form, x, idx, w, eg, eu, ed, 0, valid, routed=64,
                               row_tiles=he._ROW_TILES)
    assert picks == 6 * 470
    _check_counts(form, counts, sizes, 64 if form == "grouped" else TILE)
    assert form == "loop" or counts["expert_tiles"] == counts["experts_touched"] == 64
    assert sum(-(-s // 32) for s in sizes) == 127           # what tiles of 32 made of it
    want = np.zeros((512, 32), np.float32)
    want[:470] = _plain(x[:470], idx[:470], w[:470], eg, eu, ed, 0)
    assert np.abs(y - want).max() < 1e-4


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
    # the tiny programs' few tokens an expert: the layout's least tile
    assert eng.expert_tile_rows == 32 * eng.expert_tiles > 0
    assert sum(a["expert_tiles"] for a in seen) == eng.expert_tiles >= eng.experts_touched
    assert eng.state_summary()["moe"] == {"tokens": eng.moe_tokens,
                                          "local_picks": eng.moe_local_picks,
                                          "rows_grouped": eng.expert_rows_grouped}
