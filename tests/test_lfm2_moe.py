"""LFM2-MoE (gated short convolutions whose decode state is a SLOT's, a
grouped-query attention layer every fourth, sigmoid experts chosen on score +
bias with no shared expert) at a tiny size on the CPU, seeded random weights:
the model's own ``forward``, the serving engine's trunk over a paged K/V pool
for the attention layers AND state a slot for the conv layers (prefill in
chunks, the mixed scan, the decode scan, a slot's next tenant, a request
resumed after preemption, a row frozen inside a scan), ``short_conv`` alone
on a packed buffer, the routing, the expert layer's shares, the typed
refusals, the names in the compiled programs, the counters; all held to the
plain float32 reference (benchmark/references/conv_gqa_moe.py), which shares
nothing with the program."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine, ServingFrontend
from paddle_tpu.models import (Lfm2MoeConfig, LlamaForCausalLM, lfm2_moe, lfm2_moe_tiny,
                               llama_tiny, pangu_moe)
from paddle_tpu.ops.latent_attention import token_coords
from paddle_tpu.ops.short_conv import short_conv

from benchmark.harness import loader

import programs
from programs import ENGINE

FAMILY = loader.load_module("families", "conv_gqa_moe")
REFERENCE = loader.load_module("references", "conv_gqa_moe")
TINY, TYPES = programs.TINY["lfm2"], programs.LFM2_TYPES
# the same layers with heads of 64 (4 heads, 2 KV heads): ``lane_packing`` lays
# the two KV heads side by side, a pool block ``[1, bs, 128]``
PAIRED = dict(TINY, hidden_size=256)

# A float32 engine and the float32 reference differ by the order of their
# sums alone (a blocked online softmax against a whole one, experts added
# tile by tile against expert by expert, the taps in one order or another):
# 1e-6 to 5e-6 nats here.  1e-4 is twenty times that and a three-hundredth of
# what bf16 arithmetic gives this model (0.03-0.35 on a served token's
# log-probability: 8 mantissa bits against 24), so bf16 in a float32
# configuration fails it, and so does a state that is lost (3.4-4.6:
# ``taps1``).
LOGPROB_TOL = 1e-4


@pytest.fixture(autouse=True)
def _no_fleet_group():
    set_hybrid_communicate_group(None)


def _build(cfg=TINY, seed=7):
    return programs.build("lfm2", cfg, seed)


@pytest.fixture(scope="module")
def built():
    set_hybrid_communicate_group(None)
    return _build()


@pytest.fixture(scope="module", params=["a_head_a_row", "paired"])
def either_pool(request, built):
    """(cfg, model, weights) of TINY, whose heads of 16 lie a head a row of
    the pool, and of PAIRED, whose heads of 64 lie two to a lane tile."""
    return (TINY, *built) if request.param == "a_head_a_row" else (PAIRED, *_build(PAIRED))


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, TINY["vocab_size"], n).tolist() for n in lens]


def _ref_logprobs(weights, cfg, prompt, new, quant=None):
    """log-softmax of the reference's logits at each new token."""
    full = np.asarray(prompt + new, np.int32)
    rows = np.arange(len(prompt) - 1, len(full) - 1)
    lg = np.asarray(REFERENCE.logits_at(weights, cfg, full, rows, quant=quant), np.float64)
    lp = lg - lg.max(-1, keepdims=True)
    lp = lp - np.log(np.exp(lp).sum(-1, keepdims=True))
    return lp, lp[np.arange(len(new)), new]


def _serve(model, prompts, new=12, **engine):
    eng = ServingEngine(model, **{**ENGINE, **engine})
    rids = [eng.add_request(p, max_new_tokens=new, sampling={"logprobs": True})
            for p in prompts]
    out = eng.run()
    lps = eng.pop_token_logprobs()
    return eng, [(out[r], np.asarray(lps[r])) for r in rids]


def _held_to_reference(weights, prompts, served, tol=LOGPROB_TOL, cfg=TINY):
    for p, (new, lps) in zip(prompts, served):
        _, want = _ref_logprobs(weights, cfg, p, new)
        assert np.abs(want - lps).max() < tol, (len(p), np.abs(want - lps).max())


# ------------------------------------------------------------- the model
def test_config_keeps_the_published_names_and_refuses_another_model():
    cfg = Lfm2MoeConfig()
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_experts) == (2048, 40, 64)
    assert (cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.head_dim) == (4, 1536, 64)
    assert cfg.layers_of("full_attention") == list(range(2, 40, 4))
    assert len(cfg.layers_of("conv")) == 30 and cfg.experts_held == (0, 64)
    assert not cfg.is_sparse(1) and cfg.is_sparse(2) and cfg.rope_theta == 1e6
    # a depth cut takes the first layers of the published pattern
    assert Lfm2MoeConfig(num_hidden_layers=10, layer_types=cfg.layer_types).layer_types == (
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2)
    assert lfm2_moe_tiny().layer_types == TYPES[:6]
    with pytest.raises(ValueError, match="no range"):
        Lfm2MoeConfig(experts_held=(60, 70))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(num_hidden_layers=4, layer_types=["conv", "mamba", "conv", "conv"])
    with pytest.raises(ValueError, match="as published"):
        Lfm2MoeConfig(conv_bias=True)
    with pytest.raises(ValueError, match="as published"):
        Lfm2MoeConfig(use_expert_bias=False)


def test_the_expert_layer_has_one_definition_and_no_copy():
    for name in ("_moe_ffn", "_rms", "_swiglu"):
        assert getattr(lfm2_moe, name) is getattr(pangu_moe, name), name
    import inspect

    src = inspect.getsource(lfm2_moe)
    assert "def held_experts" not in src and "def _moe_ffn" not in src


# float32: the sums' order alone, 5e-6 at most on logits that spread by 1.
# bfloat16: the model's own arithmetic against the float32 reference moves a
# logit by 0.02-0.03 on average, what the reference's ``bf16`` witness (no
# program) reads too (0.01-0.03), and a pick of the router that flips moves
# single logits by 0.6, so the MEAN is held, at twice the witness.  A lost state
# (``taps1``) reads 1.0 on average and 5 at most: far over either limit.
@pytest.mark.parametrize("dtype, stat, tol", [("float32", np.max, 5e-5),
                                              ("bfloat16", np.mean, 0.06)])
def test_forward_agrees_with_the_reference(dtype, stat, tol):
    cfg = dict(TINY, torch_dtype=dtype)
    model, weights = _build(cfg)
    assert model.lm_head is None                    # tied: the head is the table
    ids = np.random.default_rng(1).integers(1, 256, (2, 24))
    got = np.asarray(model(P.to_tensor(ids))._value.astype(jnp.float32))
    for b in range(2):
        want = np.asarray(REFERENCE.logits_at(weights, cfg, ids[b].astype(np.int32),
                                              np.arange(24)))
        assert stat(np.abs(got[b] - want)) < tol
        lost = np.asarray(REFERENCE.logits_at(weights, cfg, ids[b].astype(np.int32),
                                              np.arange(24), quant="taps1"))
        assert stat(np.abs(lost - want)) > max(5 * tol, 0.5)


def test_the_bf16_witness_lies_between_float32_and_the_controls(built):
    _, weights = built
    ids = np.asarray(_prompts([40], seed=3)[0], np.int32)
    rows = np.arange(40)
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows))
    gap = {q: np.abs(np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows, quant=q))
                     - want).mean() for q in ("bf16", "int8", "taps1")}
    assert 0 < gap["bf16"] < gap["int8"] < gap["taps1"]


def test_lazy_guard_makes_abstract_parameters():
    model = FAMILY.build_model(TINY)
    assert all(isinstance(p._value, jax.ShapeDtypeStruct) for p in model.parameters())
    shapes = jax.eval_shape(lambda: FAMILY.make_weights(TINY, 0))
    assert model.num_params() == sum(int(np.prod(a.shape))
                                     for a in jax.tree_util.tree_leaves(shapes))


# ------------------------------------------------------- short_conv alone
def _numpy_conv(seqs, kernel):
    """Each whole sequence [S, E] through the filter, zeros before 0."""
    out = []
    for v in seqs:
        pad = np.concatenate([np.zeros((2, v.shape[1])), v])
        out.append(sum(pad[j:j + len(v)] * kernel[:, j] for j in range(3)))
    return out


def test_short_conv_on_a_packed_buffer_of_mixed_rows():
    """Row 0 decodes (one token at position 9), row 1 feeds a chunk that
    starts its sequence, row 2 a chunk that continues at position 7 (its
    first two taps come from the state), row 3 rests, row 4 feeds ONE token at
    position 0 into a slot whose state is the last tenant's, row 5 its second
    token (position 1: one tap from the state, one zero by position)."""
    rng = np.random.default_rng(5)
    E, T = 16, 24
    kernel = rng.normal(size=(E, 3))
    dec = np.array([9, 0, 7, 4, 0, 1], np.int32)
    now = np.array([1, 6, 5, 0, 1, 1], np.int32)
    seqs = [rng.normal(size=(d + n, E)) for d, n in zip(dec, now)]
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    v = np.zeros((T, E))
    state = rng.normal(size=(6, 2, E))               # garbage wherever nothing says else
    for b, (s, d, n) in enumerate(zip(seqs, dec, now)):
        v[cu[b]:cu[b] + n] = s[d:]
        for j in range(2):
            if d - 2 + j >= 0:
                state[b, j] = s[d - 2 + j]
    row, pos, valid = token_coords(T, jnp.asarray(dec), jnp.asarray(now), jnp.asarray(cu), 6)
    c, new = short_conv(jnp.asarray(v, jnp.float32), jnp.asarray(kernel, jnp.float32),
                        jnp.asarray(state, jnp.float32), row, pos, jnp.asarray(dec),
                        jnp.asarray(now), jnp.asarray(cu))
    c, new = np.asarray(c), np.asarray(new)
    want = _numpy_conv(seqs, kernel)
    for b, (d, n) in enumerate(zip(dec, now)):
        assert np.allclose(c[cu[b]:cu[b] + n], want[b][d:], atol=1e-5), b
        if n == 0:
            assert np.array_equal(new[b], state[b].astype(np.float32))    # at rest
        else:
            for j in range(2):                        # the two inputs before dec + now
                at = d + n - 2 + j
                if at >= 0:
                    assert np.allclose(new[b, j], seqs[b][at], atol=1e-6), (b, j)
    assert int(np.sum(np.asarray(valid))) == int(now.sum())


# ------------------------------------------------------ through the engine
def test_prefill_in_chunks_then_decode_against_the_reference(either_pool):
    """Five prompts on four slots: the 33-token prompt crosses the 32-token
    budget (two steps) and the mixed scan feeds the others in chunks of 8
    beside decoding rows; the fifth request takes a used slot."""
    cfg, model, weights = either_pool
    prompts = _prompts([20, 9, 33, 5, 17])
    eng, served = _serve(model, prompts)
    _held_to_reference(weights, prompts, served, cfg=cfg)
    assert eng.megasteps > eng.megasteps_mixed >= 1 and eng.prefill_chunks > 5
    assert eng.slot_state[0].shape == (5, 4, 2, cfg["hidden_size"]) and len(eng.caches[0]) == 1
    assert eng.caches[0][0].shape[1:] == ((1, 8, 128) if cfg is PAIRED else (2, 8, 16))
    assert eng.state_summary()["slot_state"] == {"arrays": ["conv"],
                                                 "rows_fed": eng.conv_rows_fed}
    assert eng.state_summary()["prefix_cache"]["enabled"] is False      # "auto" -> off


def test_the_state_is_not_something_the_tolerance_lets_go(built):
    model, weights = built
    prompts = _prompts([20, 9])
    _, served = _serve(model, prompts)
    for p, (new, lps) in zip(prompts, served):
        _, lost = _ref_logprobs(weights, TINY, p, new, quant="taps1")
        assert np.abs(lost - lps).max() > 100 * LOGPROB_TOL


def test_bf16_arithmetic_fails_the_float32_tolerance(built):
    _, weights = built
    model16, _ = _build(dict(TINY, torch_dtype="bfloat16"))
    prompts = _prompts([20])
    _, served = _serve(model16, prompts)
    new, lps = served[0]
    _, want = _ref_logprobs(weights, TINY, prompts[0], new)
    assert np.abs(want - lps).max() > 10 * LOGPROB_TOL


def test_a_slot_reused_by_a_new_request_gives_a_fresh_engines_logits(either_pool):
    """One slot: every request but the first is admitted into a slot whose
    conv state is the last tenant's.  Nothing resets it; a tap under position
    0 reads zero by position."""
    cfg, model, weights = either_pool
    prompts = _prompts([13, 1, 21, 2], seed=2)
    eng, served = _serve(model, prompts, new=9, max_batch_size=1)
    assert np.abs(np.asarray(eng.slot_state[0])).max() > 0
    _held_to_reference(weights, prompts, served, cfg=cfg)
    for p, (new, lps) in zip(prompts[1:], served[1:]):
        fresh_new, fresh_lps = _serve(model, [p], new=9, max_batch_size=1)[1][0]
        assert fresh_new == new and np.abs(fresh_lps - lps).max() < 1e-6


def test_evict_and_re_admission_give_the_identical_continuation(built):
    model, weights = built
    prompt = _prompts([19], seed=4)[0]
    _, [(whole, _)] = _serve(model, [prompt], new=20)
    eng = ServingEngine(model, **ENGINE)
    other = eng.add_request(_prompts([11], seed=9)[0], max_new_tokens=30)
    rid = eng.add_request(prompt, max_new_tokens=20)
    while rid not in eng._active or len(eng._active[rid].generated) < 6:
        eng.step()
    req = eng.evict(rid)
    assert 6 <= len(req.generated) < 20 and req.prefill_pos == 0
    again = eng.add_request(req.prompt + req.generated,
                            max_new_tokens=20 - len(req.generated),
                            sampling={"logprobs": True})
    out = eng.run()
    assert req.generated + out[again] == whole and other in out
    _, want = _ref_logprobs(weights, TINY, req.prompt + req.generated, out[again])
    assert np.abs(want - np.asarray(eng.pop_token_logprobs()[again])).max() < LOGPROB_TOL


def test_a_prefill_chunk_of_one_and_of_the_block_agree(built):
    """``prefill_chunk_tokens`` 1: every prompt token of the mixed scan reads
    BOTH earlier taps from the state; 8: six of eight from the buffer."""
    model, weights = built
    prompts = _prompts([6, 27, 14], seed=6)
    by_chunk = {}
    for chunk in (1, 8):
        eng, served = _serve(model, prompts, prefill_chunk_tokens=chunk)
        assert eng.megasteps_mixed >= 1
        _held_to_reference(weights, prompts, served)
        by_chunk[chunk] = served
    for (a, la), (b, lb) in zip(by_chunk[1], by_chunk[8]):
        assert a == b and np.abs(la - lb).max() < LOGPROB_TOL


def test_a_row_frozen_inside_the_decode_scan_keeps_its_state(built):
    """The decode scan feeds a finished row its token again at the same
    position: the pool takes the same bits, and the engine keeps the row's
    state a slot as it was when the row stopped."""
    model, weights = built
    prompts = _prompts([10, 12], seed=8)
    eng = ServingEngine(model, **ENGINE)
    short = eng.add_request(prompts[0], max_new_tokens=3)
    long = eng.add_request(prompts[1], max_new_tokens=12, sampling={"logprobs": True})
    seen = {}
    launch = eng._launch

    def spy(kind, k, block, reqs, static, **attrs):
        out = launch(kind, k, block, reqs, static, **attrs)
        if kind == "mega" and short in {r.rid for r in reqs}:
            seen["state"] = np.asarray(eng.slot_state[0])
            seen["slot"] = next(r.slot for r in reqs if r.rid == short)
        return out

    eng._launch = spy
    out = eng.run()
    assert len(out[short]) == 3 and "state" in seen
    # the state the short request left: its inputs at the last two positions
    # fed, 10 + 1 and 10 (prompt 10, tokens 0 and 1 fed back; token 2 never)
    alone = ServingEngine(model, **{**ENGINE, "megastep_k": 1})
    alone.add_request(prompts[0], max_new_tokens=3)
    alone.run()
    assert np.allclose(seen["state"][:, seen["slot"]], np.asarray(alone.slot_state[0])[:, 0],
                       atol=1e-6)
    _, want = _ref_logprobs(weights, TINY, prompts[1], out[long])
    assert np.abs(want - np.asarray(eng.pop_token_logprobs()[long])).max() < LOGPROB_TOL


def test_served_behind_the_frontend(built):
    model, weights = built
    fe = ServingFrontend([ServingEngine(model, **ENGINE)])
    prompts = _prompts([15, 8, 22], seed=11)
    rids = [fe.submit(p, max_new_tokens=7) for p in prompts]
    fe.run()
    for p, rid in zip(prompts, rids):
        new = list(fe.result(rid).tokens)
        lp, _ = _ref_logprobs(weights, TINY, p, new)
        assert (lp.argmax(-1) == np.asarray(new)).all()


# ------------------------------------------------------ the typed refusals
def test_what_takes_state_to_be_blocks_refuses_with_the_typed_error(built):
    model, _ = built
    spec = model.serving_cache_spec()
    assert spec.slot_state == (("conv", 5, (2, 64)),) and spec.layers == 1
    assert spec.slot_state in spec.key and "STATE A SLOT" in spec.why_not
    with pytest.raises(ValueError, match="prefix_cache cannot be used.*STATE A SLOT"):
        ServingEngine(model, prefix_cache=True, **ENGINE)
    with pytest.raises(ValueError, match="spec_k > 0 cannot be used.*STATE A SLOT"):
        ServingEngine(model, spec_k=2, **ENGINE)
    with pytest.raises(ValueError, match="cache_quant='int8' cannot be used.*STATE A SLOT"):
        ServingEngine(model, cache_quant="int8", **ENGINE)
    eng = ServingEngine(model, **ENGINE)                  # "auto" serves, the cache off
    assert eng.prefix_cache_enabled is False
    assert ServingEngine(model, prefix_cache=False, **ENGINE).prefix_cache_enabled is False
    for call in (lambda: eng.export_blocks(["h"]), lambda: eng.export_blocks_packed(["h"]),
                 lambda: eng.import_blocks({}), lambda: eng.import_blocks_packed({}, b"")):
        with pytest.raises(ValueError, match="STATE A SLOT"):
            call()


def test_load_weights_refuses_another_geometry(built):
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    other, _ = _build(dict(TINY, num_hidden_layers=8))     # one more attention layer
    with pytest.raises(ValueError, match="geometry"):
        eng.load_weights(other)
    again, _ = _build(seed=8)
    assert eng.load_weights(again, version="v1") == "v1"


# ------------------------------------------------------------ the routing
def test_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.2, jnp.float32)
    idx, wt = lfm2_moe.route_biased(x, w, bias, 4, 1.0)
    ridx, rwt = REFERENCE.route(x, w, bias, 4, 1.0)
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(ridx)))
    g = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(w, np.float64)))
    want_idx = np.argsort(-(g + np.asarray(bias, np.float64)), axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(want_idx))
    plain, _ = lfm2_moe.route_biased(x, w, jnp.zeros(16), 4, 1.0)
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(idx))).any()   # the bias chose
    # the weights: the chosen scores WITHOUT the bias over (their sum + 1e-6), scale 1
    chosen = np.take_along_axis(g, np.asarray(idx), axis=-1)
    assert np.allclose(np.asarray(wt), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                       atol=1e-6)
    assert np.all(np.asarray(wt).sum(-1) < 1.0) and np.allclose(np.asarray(wt).sum(-1), 1, 1e-5)
    _, twice = lfm2_moe.route_biased(x, w, bias, 4, 2.0)
    assert np.allclose(np.asarray(twice), 2 * np.asarray(wt), atol=1e-6)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """8 shares of 8 of 64 experts: with no shared expert to count once, the
    shares' results simply add up to the layer's."""
    cfg = dict(TINY, num_experts=64, num_experts_per_tok=4)
    whole_cfg = FAMILY.model_config(cfg)
    _, weights = _build(cfg)
    p = weights["layers"][2]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)), jnp.float32)
    whole, picks = lfm2_moe._ffn(whole_cfg, p, x)
    assert int(picks) == 24 * 4
    total, counted = 0.0, 0
    for lo in range(0, 64, 8):
        share = FAMILY.model_config(dict(cfg, experts_held=[lo, lo + 8]))
        leaves = dict(p, eg=p["eg"][lo:lo + 8], eu=p["eu"][lo:lo + 8], ed=p["ed"][lo:lo + 8])
        y, n = lfm2_moe._ffn(share, leaves, x)
        total, counted = total + y, counted + int(n)
    assert counted == 24 * 4 and np.abs(np.asarray(total - whole)).max() < 1e-5
    idx, w = REFERENCE.route(x, p["router"], p["router_bias"], 4, 1.0)
    ref = sum(REFERENCE.weight_of(idx, w, e)[:, None]
              * REFERENCE.swiglu(x, p["eg"][e], p["eu"][e], p["ed"][e]) for e in range(64))
    assert np.abs(np.asarray(whole - ref)).max() < 1e-5


# ------------------------------------------------- names, spans and counters
LOOP = "(?:while/body/)+"
SCOPES = ("embed", "norm", "conv_proj", "short_conv", "conv_out", "attn_proj", "attention",
          "paged_attention", "paged_attention/rope", "paged_attention/kv_write", "attn_out",
          "router", "experts", "experts/while/body", "mlp", "head", "sample")


@pytest.fixture(scope="module")
def lfm2_texts(built):
    return programs.lowered(ServingEngine(built[0], **ENGINE), debug_info=True,
                              kinds=("step", "mega", "mixed"))


@pytest.mark.parametrize("kind", ["step", "mega", "mixed"])
def test_lowered_program_names_the_scopes(lfm2_texts, kind):
    text = lfm2_texts[kind]
    want = SCOPES + (() if kind == "step" else ("scan_carry",))
    missing = [s for s in want if not re.search(rf'["/(]{s}[/)"]', text)]
    assert not missing, f"{kind}: no operation under {missing}"
    assert f"jit_{kind}" in text and "shared_expert" not in text


def test_the_counters_are_monotone_and_ride_the_harvest_span(built):
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    harvests = programs.harvests(eng)
    names = ("conv_rows_fed", "moe_tokens", "moe_local_picks", "experts_touched",
             "expert_tiles", "expert_tile_rows", "expert_tile_rows_live", "expert_rows_grouped",
             "attn_positions_live", "kv_write_tokens", "attn_rows_kernel", "kv_write_blocks")
    assert all(getattr(eng, n) == 0 for n in names)
    for p in _prompts([20, 9]):
        eng.add_request(p, max_new_tokens=6)
    last = (0,) * len(names)
    while eng._queue or eng._active:
        eng.step()
        now = tuple(getattr(eng, n) for n in names)
        assert all(a >= b for a, b in zip(now, last))
        last = now
    fed = 20 + 9 + 5 + 5                     # prompt tokens and the tokens fed back
    assert eng.kv_write_tokens == fed and eng.moe_tokens == 4 * fed     # 4 expert layers
    assert eng.attn_rows_kernel == 0 == eng.kv_write_blocks     # the CPU: XLA and the scatter
    assert eng.moe_local_picks == eng.expert_tile_rows_live == 2 * eng.moe_tokens
    assert eng.expert_tile_rows >= eng.expert_tile_rows_live
    assert 0 < eng.experts_touched <= 8 * 4 * eng.launches * eng.megastep_k
    # a row-layer a launch iteration that fed the row: both prompts in one
    # step, then 5 decode iterations of 2 rows, over 5 conv layers
    assert eng.conv_rows_fed == 5 * (2 + 2 * 5)
    assert eng.state_summary()["experts"] == {
        "touched": eng.experts_touched, "tiles": eng.expert_tiles,
        "tile_rows": eng.expert_tile_rows,
        "tile_rows_live": eng.expert_tile_rows_live}
    assert eng.state_summary()["moe"] == {"tokens": eng.moe_tokens,
                                          "local_picks": eng.moe_local_picks,
                                          "rows_grouped": 0}    # the CPU: the tile loop
    seen = [h[-1] for h in harvests]
    assert seen and all(set(names) <= set(a) for a in seen)
    for n in names:
        assert sum(a[n] for a in seen) == getattr(eng, n), n


def test_an_engine_steered_onto_the_chip_sends_heads_of_64_through_both_kernels(monkeypatch):
    """A bf16 PAIRED model over blocks of 16 is a call both kernels admit once
    its heads lie two to a lane tile.  With ``on_tpu`` answering yes (the
    kernels in interpret mode) every one-token row of every scan iteration
    attends through ``paged_decode`` and every write is ``paged_write``'s:
    the trunk counts them as models/llama.py's does, the tokens are the
    unsteered engine's."""
    from paddle_tpu.inference import serving
    from paddle_tpu.ops import paged_attention as pa

    import test_paged_attention

    model, _ = _build(dict(PAIRED, torch_dtype="bfloat16"))
    prompts = _prompts([5, 22], seed=3)

    def run():
        eng = ServingEngine(model, **dict(ENGINE, block_size=16, max_batch_size=2))
        harvests = programs.harvests(eng)
        rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
        out = eng.run()
        return eng, [h[-1] for h in harvests], [out[r] for r in rids]

    plain, _, want = run()
    assert plain.attn_rows_kernel == 0 == plain.kv_write_blocks
    monkeypatch.setattr(serving, "_PROGRAM_CACHE", {})
    test_paged_attention._steer_onto_the_chip(monkeypatch, chunks=True)
    pa.blha_attention.clear_cache()     # the one jitted function that asks the platform
    try:
        eng, seen, got = run()
    finally:
        pa.blha_attention.clear_cache()
    assert got == want
    # both prompts in one prefill step (chunk rows: ``paged_chunk``, over the packed
    # pool as the caller hands it), then each row decodes its other 8 tokens a row a
    # scan iteration
    assert eng.attn_rows_kernel == 2 * 8 == sum(a["attn_rows_kernel"] for a in seen)
    assert eng.attn_chunks_kernel == 2 == sum(a["attn_chunks_kernel"] for a in seen)
    assert eng.attn_positions_live == plain.attn_positions_live
    assert eng.attn_positions_read < plain.attn_positions_read
    # the scatter's tokens; the prompts lie in one piece of 16 positions and
    # in two, a token fed back in one
    assert eng.kv_write_tokens == plain.kv_write_tokens == 27 + 16
    assert eng.kv_write_blocks == 1 + 2 + 16 == sum(a["kv_write_blocks"] for a in seen)


def test_a_model_without_state_a_slot_counts_none_and_has_none():
    P.seed(0)
    eng = ServingEngine(LlamaForCausalLM(llama_tiny()).eval(), **ENGINE)
    eng.add_request([3, 17, 101], max_new_tokens=6)
    eng.run()
    assert eng.slot_state == () and eng.program_caches() == tuple(eng.caches)
    assert eng.state_summary()["slot_state"] == {"arrays": [], "rows_fed": 0}
    assert eng.state_summary()["experts"] == {"touched": 0, "tiles": 0, "tile_rows": 0,
                                              "tile_rows_live": 0}
