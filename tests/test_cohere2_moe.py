"""Command A+ (``cohere2_moe``: a PARALLEL block whose one LayerNorm feeds
grouped-query attention, four averaged shared experts and sigmoid-routed experts,
all three joining the residual together; three sliding-window layers with
interleaved RoPE then a global one without position encoding; a head that is
the embedding table) at a tiny size on the CPU, seeded random weights: the
model's own ``forward``; the serving engine's trunk over TWO pools, a block
table a KIND of cache layer though layer 0 is a window layer (the step, the
decode scan, the mixed scan; contexts that cross the window several times; two
slots over a window pool so small that blocks change hands); the shares of a
deployment adding up to the uncut layer; the sliced head; the controls being
controls; the LayerNorm; the two RoPE pairings; the refusals; the names in the
compiled programs; the counters; all held to the plain float32 reference
(benchmark/references/parallel_swa_moe.py), which shares nothing with the
program.  Window 24 positions = 3 blocks of 8; 16 routed experts of which this
chip holds [4, 12)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine, ServingFrontend
from paddle_tpu.inference.serving import head_logits
from paddle_tpu.inference.serving_model import CacheKind
from paddle_tpu.models import Cohere2MoeConfig, cohere2_moe, cohere2_moe_tiny
from paddle_tpu.ops.latent_attention import rope_half
from paddle_tpu.ops.paged_attention import rope_rotate
from paddle_tpu.ops.norms import layer_norm

from benchmark.harness import loader

import programs
from programs import ENGINE

FAMILY = loader.load_module("families", "parallel_swa_moe")
REFERENCE = loader.load_module("references", "parallel_swa_moe")
TINY = programs.TINY["cohere2"]
W, BS = TINY["sliding_window"], ENGINE["block_size"]
CONTROLS = REFERENCE.MECHANISM
POOLS = {"global": 48, "window": 28}

# A float32 engine and the float32 reference differ by the order of their sums
# alone (a blocked online softmax against a whole one, one wide shared SwiGLU
# against four, experts tile by tile against expert by expert): about 1e-6 nats
# on a served token's log-probability.  1e-4 is a hundred times that; each
# control OF THE MECHANISM moves it by 0.05 nats and more on a context past
# the window, five hundred times the tolerance, and so does bf16 arithmetic.
LOGPROB_TOL = 1e-4


@pytest.fixture(autouse=True)
def _no_fleet_group():
    set_hybrid_communicate_group(None)


@pytest.fixture(scope="module")
def built():
    return programs.build("cohere2")


def _prompts(lens, seed=0):
    return programs.prompts(lens, seed, TINY["vocab_size"])


def _ref_logprobs(weights, prompt, new, quant=None, cfg=TINY):
    """log-softmax of the reference's logits at each new token."""
    full = np.asarray(prompt + new, np.int32)
    rows = np.arange(len(prompt) - 1, len(full) - 1)
    lg = np.asarray(REFERENCE.logits_at(weights, cfg, full, rows, quant=quant), np.float64)
    lp = lg - lg.max(-1, keepdims=True)
    lp = lp - np.log(np.exp(lp).sum(-1, keepdims=True))
    return lp, lp[np.arange(len(new)), new]


def _serve(model, prompts, new=12, **engine):
    eng = ServingEngine(model, **{**ENGINE, "num_blocks": POOLS, **engine})
    rids = [eng.add_request(p, max_new_tokens=new, sampling={"logprobs": True})
            for p in prompts]
    out = eng.run()
    lps = eng.pop_token_logprobs()
    return eng, [(out[r], np.asarray(lps[r])) for r in rids]


def _held_to_reference(weights, prompts, served, tol=LOGPROB_TOL):
    for p, (new, lps) in zip(prompts, served):
        _, want = _ref_logprobs(weights, p, new)
        assert np.abs(want - lps).max() < tol, (len(p), np.abs(want - lps).max())


# ------------------------------------------------------------- the model
def test_config_keeps_the_published_names():
    cfg = Cohere2MoeConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts, cfg.intermediate_size, cfg.sliding_window,
            cfg.vocab_size, cfg.rope_theta, cfg.layer_norm_eps, cfg.logit_scale) == (
                32, 4096, 128, 8, 128, 128, 8, 4, 4096, 4096, 262144, 50000.0, 1e-5, 1.0)
    assert cfg.layer_types[:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert len(cfg.layers_of(True)) == 24 and cfg.layers_of(False) == list(range(3, 32, 4))
    assert cfg.num_attention_heads * cfg.head_dim == 4 * cfg.hidden_size      # 16,384
    assert (cfg.shared_expert_combination_strategy, cfg.expert_selection_fn,
            cfg.position_embedding_type, cfg.use_parallel_block, cfg.tie_word_embeddings,
            cfg.norm_topk_prob) == ("average", "sigmoid", "rope_gptj", True, True, True)
    built = FAMILY.model_config(TINY)
    assert built.num_experts == 16 and built.experts_held == (4, 12)     # the router's width
    assert built.layer_types == ["sliding_attention"] * 3 + ["full_attention"]


@pytest.mark.parametrize("bad", [
    dict(use_qk_norm=True), dict(use_parallel_block=False),
    dict(shared_expert_combination_strategy="sum"), dict(first_k_dense_replace=1),
    dict(rotary_pct=0.5), dict(tie_word_embeddings=False), dict(expert_selection_fn="softmax"),
    dict(position_embedding_type="rope"), dict(layer_types=["sliding_attention"] * 4),
    dict(layer_types=["full_attention", "linear_attention"] * 2), dict(experts_held=(4, 17)),
], ids=lambda d: next(iter(d)))
def test_the_config_refuses_what_the_model_does_not_build(bad):
    with pytest.raises(ValueError):
        cohere2_moe_tiny(**bad)


def test_layer_norm_takes_the_mean_out_and_has_no_bias():
    """Against ``nn.LayerNorm`` without a bias, in float32; in bfloat16 the
    arithmetic stays float32 inside."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(2.0, 3.0, size=(7, 64)), jnp.float32)
    g = jnp.asarray(rng.normal(1.0, 0.2, size=(64,)), jnp.float32)
    ln = P.nn.LayerNorm(64, epsilon=1e-5, bias_attr=False)
    ln.weight._value = g
    got = layer_norm(x, g, None, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ln(P.to_tensor(x))._value),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(REFERENCE.layer_norm(x, g, 1e-5)),
                               rtol=1e-6, atol=1e-6)
    assert abs(float(jnp.mean(got / g))) < 1e-5            # the mean IS taken out
    rms = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * g
    assert np.abs(np.asarray(got - rms)).max() > 0.3       # an RMSNorm is another function
    low = layer_norm(x.astype(jnp.bfloat16), g.astype(jnp.bfloat16), None, 1e-5)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), np.asarray(got), atol=0.05)
    with_bias = layer_norm(x, g, jnp.ones((64,)), 1e-5)      # the selector's form, as it was
    np.testing.assert_allclose(np.asarray(with_bias), np.asarray(got) + 1.0, rtol=1e-6)


def test_interleaved_and_half_paired_rope_differ():
    """``rope_gptj`` turns the pairs (2i, 2i + 1); the other trunks turn (i, i +
    D/2).  The same table, another function, equal under the permutation that
    maps one pairing to the other; the reference's own agrees with the op."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(9, 2, 16)), jnp.float32)
    table = cohere2_moe.rope_table(cohere2_moe_tiny(), 9)
    cos, sin = table[0][:, None, :], table[1][:, None, :]
    pairs = rope_rotate(x, cos, sin, neox=False)
    halves = rope_rotate(x, cos, sin, neox=True)
    assert np.abs(np.asarray(pairs - halves))[1:].max() > 0.1
    np.testing.assert_array_equal(np.asarray(pairs[0]), np.asarray(x[0]))      # position 0
    np.testing.assert_allclose(np.asarray(halves), np.asarray(rope_half(x, table[0], table[1])),
                               rtol=1e-6)
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])          # pairs -> halves
    np.testing.assert_allclose(
        np.asarray(pairs)[..., perm],
        np.asarray(rope_rotate(x[..., perm], cos, sin, neox=True)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(REFERENCE.rope_pairs(x, 10000.0)), np.asarray(pairs),
                               rtol=1e-5, atol=1e-5)


def test_forward_agrees_with_the_reference(built):
    """Whole sequences under an explicit mask, 70 tokens over a window of 24."""
    model, weights = built
    ids = np.asarray(_prompts([70], seed=2)[0], np.int32)
    got = np.asarray(model(jnp.asarray(ids[None]))._value[0], np.float64)
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(len(ids))), np.float64)
    assert np.abs(got - want).max() < 5e-5


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_moves_the_references_logits(built, control):
    """So the controls are controls: ``serial_block`` (the feed-forward reads
    ``LN(x + Attn(u))``), ``shared_sum`` (the shared experts summed),
    ``rope_all`` (RoPE on the global layer too), ``window_off`` (the window
    forgotten), each far over the tolerance the engine is held to; the window is
    a difference only PAST it, the other three from the first rows."""
    _, weights = built
    ids = np.asarray(_prompts([70], seed=2)[0], np.int32)
    rows = np.arange(len(ids))
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows), np.float64)
    off = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows, quant=control), np.float64)
    assert np.abs(off - want).max() > 0.05 >= 500 * LOGPROB_TOL
    short = np.abs(off[:W] - want[:W]).max()
    assert short < 1e-5 if control == "window_off" else short > 0.05


def test_misplaced_hands_out_the_row_befores_logits(built):
    """``quant="misplaced"``: row r gets the SOUND logits of row r - 1 (row 0 its
    own), so every pick from them is a right token in the wrong place: what the
    cell's ``max_gap_nats`` is read against."""
    _, weights = built
    ids = np.asarray(_prompts([40], seed=3)[0], np.int32)
    rows = np.arange(5, len(ids))
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows - 1))
    got = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows, quant="misplaced"))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    first = np.asarray(REFERENCE.logits_at(weights, TINY, ids, [0], quant="misplaced"))
    np.testing.assert_allclose(first, np.asarray(REFERENCE.logits_at(weights, TINY, ids, [0])),
                               rtol=0, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(built):
    """A layer's routed part over the ranges [2k, 2k + 2) of 16 experts, eight
    shares as the deployment's eight chips hold them, with ``Shared(u)`` and
    ``Attn(u)`` counted ONCE, sums to the uncut reference's layer; and the
    program's own layer at one share is the reference's at that share."""
    cfg = dict(TINY, num_experts=16, experts_held=[0, 16])
    p = FAMILY.make_weights(cfg, 11)["layers"][1]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(40, 64)), jnp.float32)
    uncut = np.asarray(REFERENCE.layer_forward(p, x, cfg, 1), np.float64)

    def share(lo, hi, routed=True):
        cut = dict(p, **{k: p[k][lo:hi] for k in ("eg", "eu", "ed")})
        if not routed:
            cut["ed"] = cut["ed"] * 0
        return cut, np.asarray(REFERENCE.layer_forward(cut, x, cfg, 1, held=(lo, hi)), np.float64)

    # what every chip computes alike, x + Attn(u) + Shared(u): counted once
    alike = share(0, 2, routed=False)[1]
    parts = [share(2 * k, 2 * k + 2)[1] - alike for k in range(8)]
    assert all(np.abs(part).max() > 1e-3 for part in parts)       # every share takes rows
    assert np.abs(alike + sum(parts) - uncut).max() < 1e-4
    assert np.abs(alike + sum(parts[:7]) - uncut).max() > 1e-3    # and none may be left out
    # the program's layer at the share [4, 12) is the reference's at that share
    mc = FAMILY.model_config(dict(cfg, torch_dtype="float32"), experts_held=(4, 12))
    cut, want = share(4, 12)
    got = cohere2_moe._layer_full(mc, cut, x[None], 1)[0]
    assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-4


def test_the_sliced_head_is_the_whole_heads_columns(built):
    """A sliced vocabulary is a smaller one: over ids of the slice [0, 64), the
    table's rows [0, 64) give the whole head's columns [0, 64), in the reference
    and in the program; the engine heads by the table itself, no second matrix."""
    model, weights = built
    ids = np.random.default_rng(5).integers(1, 64, 50).astype(np.int32)
    rows = np.arange(len(ids))
    whole = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows))
    cut_cfg = dict(TINY, vocab_size=64)
    cut_weights = dict(weights, embed=weights["embed"][:64])
    cut = np.asarray(REFERENCE.logits_at(cut_weights, cut_cfg, ids, rows))
    assert cut.shape == (50, 64) and np.abs(cut - whole[:, :64]).max() < 1e-5
    sliced = FAMILY.build_model(cut_cfg)
    FAMILY.assign(sliced, cut_weights)
    got = np.asarray(sliced.eval()(jnp.asarray(ids[None]))._value[0])
    assert got.shape == (50, 64) and np.abs(got - whole[:, :64]).max() < 5e-5
    w = model.serving_weights(jnp.float32)
    assert "head" not in w and w["embed"] is model.model.embed_tokens.weight._value
    assert FAMILY.params_of(model)["embed"] is model.model.embed_tokens.weight     # ONE leaf
    assert [n for n, _ in model.named_parameters() if "lm_head" in n] == []
    h = jnp.asarray(np.random.default_rng(6).normal(size=(3, 64)), jnp.float32)
    np.testing.assert_allclose(np.asarray(head_logits(h, w)), np.asarray(h @ w["embed"].T),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(head_logits(h, {"head": w["embed"].T})),
                                  np.asarray(h @ w["embed"].T))


# ---------------------------------------------------- the engine, every launch
@pytest.mark.parametrize("launches, engine", [
    ("step", dict(megastep_k=1)),
    ("mega", dict(megastep_k=4, token_budget=96)),       # prompts whole, then the decode scan
    ("mixed", dict(megastep_k=4)),                        # prompts in chunks beside decoding rows
    ("mixed_chunk3", dict(megastep_k=4, prefill_chunk_tokens=3)),
])
def test_every_launch_kind_agrees_with_the_reference_across_the_window(built, launches, engine):
    """Contexts of 9 to 92 positions over a window of 24: the longest crosses it
    nearly four times.  Each served token's log-probability within 1e-4 of the
    reference's full forward (logits, not tokens), which every control fails;
    the window kind's blocks were given back on the way and taken again by other
    rows, though layer 0 is a window layer and kind ``global`` the first table."""
    model, weights = built
    prompts = _prompts([70, 40, 9, 55, 31, 62], seed=1)
    eng, served = _serve(model, prompts, new=22, **engine)
    _held_to_reference(weights, prompts, served)
    p, (new, lps) = prompts[0], served[0]
    for control in CONTROLS:
        _, off = _ref_logprobs(weights, p, new, quant=control)
        assert np.abs(off - lps).max() > 500 * LOGPROB_TOL, control
    st = eng.state_summary()
    assert st["window_blocks_released"] > 0
    assert {"step": eng.megasteps == 0, "mega": eng.megasteps > eng.megasteps_mixed,
            "mixed": eng.megasteps_mixed > 0, "mixed_chunk3": eng.megasteps_mixed > 0}[launches]
    assert [p["blocks_held"] for p in st["pools"]] == [0, 0]
    assert all(m.num_free == m.num_blocks for m in eng.pools)


def test_two_slots_over_a_window_pool_whose_blocks_change_hands(built):
    """Two slots over a window pool so small that every block is taken again and
    again: a block the window gave back and another row took is never read by the
    first (its table entry reads -1 behind every window at every step), so every
    token stays the reference's."""
    model, weights = built
    prompts = _prompts([66, 12, 81, 33], seed=5)
    eng = ServingEngine(model, **{**ENGINE, "max_batch_size": 2,
                                  "num_blocks": {"global": 30, "window": 18}})
    given, taken = [], []
    free, allocate = eng.pools[1].free, eng.pools[1].allocate
    eng.pools[1].free = lambda blocks: (given.extend(blocks), free(blocks))[1]
    eng.pools[1].allocate = lambda n: (lambda got: (taken.append(list(got)), got)[1])(allocate(n))
    rids = [eng.add_request(p, max_new_tokens=14, sampling={"logprobs": True}) for p in prompts]
    while eng._queue or eng._active:
        eng.step()
        now = {}
        for rid, r in eng._active.items():
            for col, b in (r.kind_blocks[0] if r.kind_blocks else {}).items():
                assert b not in now, "one window block, two rows"
                now[b] = rid
                assert eng.kind_tables[1][r.slot, col] == b
            if r.slot >= 0:     # behind the window the table names nothing
                assert (eng.kind_tables[1][r.slot, :max(r.cached_len - W + 1, 0) // BS] == -1).all()
    out, lps = dict(eng._finished), eng.pop_token_logprobs()
    _held_to_reference(weights, prompts, [(out[r], np.asarray(lps[r])) for r in rids])
    first_owner = set(taken[0])
    assert first_owner & set(given) and any(first_owner & set(t) for t in taken[1:])
    assert sum(len(t) for t in taken) > 18 and eng.window_blocks_released > 0   # reuse


def test_served_behind_the_frontend(built):
    model, weights = built
    fe = ServingFrontend([ServingEngine(model, **{**ENGINE, "num_blocks": POOLS})])
    prompts = _prompts([45, 8, 72], seed=11)
    rids = [fe.submit(p, max_new_tokens=9) for p in prompts]
    fe.run()
    for p, rid in zip(prompts, rids):
        new = list(fe.result(rid).tokens)
        lp, _ = _ref_logprobs(weights, p, new)
        assert (lp.argmax(-1) == np.asarray(new)).all()


# ------------------------------------------------------ kinds, tables, refusals
def test_the_global_kind_stays_first_though_layer_0_is_a_window_layer(built):
    model, _ = built
    spec = model.serving_cache_spec()
    assert spec.kinds == (CacheKind("global", 1), CacheKind("window", 3, W))
    assert spec.layers == 4 and not spec.blocks_are_positions and W in spec.key
    assert not spec.quantizable and not spec.transferable
    eng = ServingEngine(model, **{**ENGINE, "num_blocks": POOLS})
    assert [m.num_blocks for m in eng.pools] == [48, 28] and eng.blocks is eng.pools[0]
    # the caches lie a kind after another: the global layer (layer 3) first
    assert [a.shape[0] for a in eng.caches[0]] == [48, 28, 28, 28]
    assert len(eng.kind_tables) == 2
    plain = FAMILY.build_model(dict(TINY, layer_types=["full_attention"] * 4))
    assert plain.serving_cache_spec().kinds == (CacheKind("global", 4),)
    assert plain.serving_cache_spec().blocks_are_positions


@pytest.mark.parametrize("what", ["prefix_cache", "spec_k", "cache_quant", "export", "import"])
def test_what_takes_blocks_to_be_all_positions_refuses_with_the_typed_error(built, what):
    model, _ = built
    why = "GIVE BACK the blocks behind"
    assert why in model.serving_cache_spec().why_not
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix_cache cannot be used.*" + why):
            ServingEngine(model, prefix_cache=True, **ENGINE)
        assert ServingEngine(model, **ENGINE).prefix_cache_enabled is False     # "auto" serves
    elif what == "spec_k":
        with pytest.raises(ValueError, match="spec_k > 0 cannot be used.*" + why):
            ServingEngine(model, spec_k=2, **ENGINE)
    elif what == "cache_quant":
        with pytest.raises(ValueError, match="cache_quant='int8' cannot be used.*" + why):
            ServingEngine(model, cache_quant="int8", **ENGINE)
    else:
        eng = ServingEngine(model, **ENGINE)
        calls = {"export": (lambda: eng.export_blocks(["h"]),
                            lambda: eng.export_blocks_packed(["h"])),
                 "import": (lambda: eng.import_blocks({}),
                            lambda: eng.import_blocks_packed({}, b""))}[what]
        for call in calls:
            with pytest.raises(ValueError, match=why):
                call()


# ------------------------------------------------------- names and counters
SCOPES = ("embed", "norm", "attn_proj", "router", "paged_attention", "rope", "kv_write",
          "attn_out", "shared_experts", "experts", "head")


@pytest.mark.parametrize("kind", ["step", "mega", "mixed"])
def test_lowered_program_names_the_scopes_and_the_feed_forward_reads_the_norms_rows(built, kind):
    eng = ServingEngine(built[0], **{**ENGINE, "num_blocks": POOLS})
    text = programs.lowered(eng, debug_info=True, kinds=(kind,))[kind]
    want = SCOPES + (() if kind == "step" else ("scan_carry",))
    missing = [s for s in want if not re.search(rf'["/(]{s}[/)"]', text)]
    assert not missing, f"{kind}: no operation under {missing}"
    assert f"jit_{kind}" in text
    # ONE norm a layer and a final one: five reductions' worth of scope ``norm``,
    # not the nine of a serial block of two norms a layer
    assert "post_attention" not in text


def test_the_counters_are_monotone_and_ride_the_harvest_span(built):
    model, _ = built
    eng = ServingEngine(model, **{**ENGINE, "num_blocks": POOLS})
    harvests = programs.harvests(eng)
    names = ("moe_tokens", "moe_local_picks", "experts_touched", "expert_tiles",
             "expert_tile_rows", "expert_tile_rows_live", "expert_rows_grouped",
             "attn_positions_live", "attn_positions_read", "kv_write_tokens")
    by_kind = ("attn_positions_live.global", "attn_positions_read.global",
               "attn_positions_live.window", "attn_positions_read.window",
               "window_positions_spared")
    for p in _prompts([60, 9]):
        eng.add_request(p, max_new_tokens=6)
    last = (0,) * (len(names) + len(by_kind) + 1)
    while eng._queue or eng._active:
        eng.step()
        now = (tuple(getattr(eng, n) for n in names)
               + tuple(eng.kind_counts.get(n, 0) for n in by_kind)
               + (eng.window_blocks_released,))
        assert all(a >= b for a, b in zip(now, last))
        last = now
    # the prompts, the tokens fed back, and ONE token a row frozen in the last
    # decode scan was fed again (the same bits at the same position)
    fed = 60 + 9 + 5 + 5 + 1
    assert eng.kv_write_tokens == fed and eng.moe_tokens == 4 * fed     # 4 expert layers
    # 4 picks a token of 16 experts, of which 8 are held here: about half fall on one
    assert 0.3 * 4 * eng.moe_tokens < eng.moe_local_picks < 0.7 * 4 * eng.moe_tokens
    assert eng.moe_local_picks == eng.expert_tile_rows_live
    kc = eng.kind_counts
    assert kc["attn_positions_live.global"] == kc["attn_positions_live.window"] \
        == eng.attn_positions_live
    assert kc["attn_positions_read.global"] == eng.attn_positions_read
    assert kc["window_positions_spared"] == (32 - 23) + sum(
        d - (W - 1) for d in (60, 61, 62, 63, 64, 64))
    assert eng.window_blocks_released == (64 - W + 1) // BS == 5
    st = eng.state_summary()
    assert st["attention_by_kind"] == kc
    assert [p["kind"] for p in st["pools"]] == ["global", "window"]
    seen = [h[-1] for h in harvests]
    assert seen and all(set(names + by_kind) <= set(a) for a in seen)
    for n in names:
        assert sum(a[n] for a in seen) == getattr(eng, n), n
    for n in by_kind:
        assert sum(a[n] for a in seen) == kc[n], n
