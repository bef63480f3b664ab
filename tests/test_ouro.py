"""Ouro (a stack of layers run ``total_ut_steps`` times over shared weights)
at a tiny size on the CPU, seeded random weights: the model's own ``forward``,
the serving engine's rolled trunk over ONE paged pool with a layer axis
(prefill, the mixed scan, the decode scan, a resumed request, the prefix
cache and its COW fork, block export), the exit gate, the typed refusals, the
names in the compiled programs, the counters; all held to the plain float32
reference (benchmark/references/looped_dense.py), which shares nothing with
the program."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models import LlamaForCausalLM, OuroConfig, OuroForCausalLM, llama_tiny
from paddle_tpu.models import ouro
from paddle_tpu.ops.paged_attention import blha_attention

from benchmark.harness import loader

import programs
from programs import ENGINE

FAMILY = loader.load_module("families", "looped_dense")
REFERENCE = loader.load_module("references", "looped_dense")
TINY = programs.TINY["ouro"]

# A float32 engine and the float32 reference differ by the order of their
# sums alone (a blocked online softmax against a whole one, XLA's matmuls
# against ``highest``): 3e-6 nats here over 12 layer applications.  1e-4 is
# thirty times that and a fiftieth of what bf16 arithmetic gives (0.005-0.05:
# 8 mantissa bits against 24), so bf16 in a float32 configuration fails it.
LOGPROB_TOL = 1e-4


@pytest.fixture(autouse=True)
def _no_fleet_group():
    set_hybrid_communicate_group(None)


def _build(cfg=TINY, seed=7):
    return programs.build("ouro", cfg, seed)


@pytest.fixture(scope="module")
def built():
    set_hybrid_communicate_group(None)
    return _build()


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, TINY["vocab_size"], n).tolist() for n in lens]


def _ref_logprobs(weights, cfg, prompt, new):
    """log-softmax of the reference's logits at each new token."""
    full = np.asarray(prompt + new, np.int32)
    rows = np.arange(len(prompt) - 1, len(full) - 1)
    lg = np.asarray(REFERENCE.logits_at(weights, cfg, full, rows), np.float64)
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


# ------------------------------------------------------------- the model
def test_config_keeps_the_published_names_and_counts_the_published_model():
    cfg = OuroConfig()
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.total_ut_steps,
            cfg.early_exit_threshold) == (2048, 48, 4, 1.0)
    with P.LazyGuard():
        model = OuroForCausalLM(OuroConfig(dtype="bfloat16"))
    assert isinstance(model.lm_head.weight._value, jax.ShapeDtypeStruct)
    assert model.num_params() == 2_667_974_657          # the issue's arithmetic, gate and bias in it
    spec = model.serving_cache_spec()
    assert (spec.layers, spec.passes, spec.stacked) == (192, 4, True)
    assert (spec.kv_heads, spec.head_dim) == (16, 128)


def test_a_threshold_under_one_is_refused_with_its_reason():
    with pytest.raises(ValueError, match="not served.*different numbers of passes"):
        OuroConfig(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="not served"):
        FAMILY.model_config(dict(TINY, early_exit_threshold=0.5))
    with pytest.raises(ValueError, match="at least one pass"):
        OuroConfig(total_ut_steps=0)
    with pytest.raises(ValueError):
        REFERENCE.logits_at({}, dict(TINY, early_exit_threshold=0.5), [1, 2], [0])


def test_forward_agrees_with_the_reference_pass_by_pass(built):
    model, weights = built
    ids = np.asarray(_prompts([40])[0], np.int32)
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(40)))
    got = np.asarray(model(P.to_tensor(ids[None]))._value)[0]
    assert np.abs(got - want).max() < 2e-5
    every, lam = model(P.to_tensor(ids[None]), all_passes=True)
    passes = np.asarray(REFERENCE.pass_logits_at(weights, TINY, ids, np.arange(40)))
    assert passes.shape == (4, 40, 256)
    assert np.abs(np.asarray(every._value)[:, 0] - passes).max() < 2e-5
    assert np.abs(passes[-1] - want).max() == 0 and np.abs(passes[0] - want).max() > 0.1
    low = np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(40), quant="int8"))
    assert 1e-3 < np.abs(low - want).max()          # the control moves them
    with pytest.raises(ValueError):
        REFERENCE.logits_at(weights, TINY, ids, np.arange(40), quant="int3")


def test_the_exit_gate_gives_the_references_distribution():
    # a gate that is not all but shut, so that every pass gets a share
    weights = FAMILY.make_weights(TINY, 11)
    weights["gate_b"] = weights["gate_b"] + 0.5
    model = FAMILY.build_model(TINY)
    FAMILY.assign(model, weights)
    ids = np.asarray(_prompts([24], seed=3)[0], np.int32)
    _, lam = model(P.to_tensor(ids[None]), all_passes=True)
    lam = np.asarray(lam._value)[:, 0]
    want_lam, want_p = (np.asarray(a) for a in REFERENCE.exit_probabilities(weights, TINY, ids))
    assert lam.shape == (4, 24) and np.abs(lam - want_lam).max() < 1e-5
    p = np.asarray(ouro.exit_distribution(jnp.asarray(lam)))
    assert np.abs(p - want_p).max() < 1e-5
    assert np.allclose(p.sum(0), 1.0, atol=1e-6) and (p > 0).all() and p[1:].max() > 0.1
    # the last pass takes what is left, whatever its own gate says
    assert np.allclose(p[-1], np.prod(1.0 - lam[:-1], axis=0), atol=1e-6)


# -------------------------------------------------- engine against reference
def test_prefill_then_decode_through_the_paged_cache(built):
    """Prompts shorter and longer than a launch's budget, so the single-step
    prefill, the mixed scan (prompt chunks of 8) and the decode scan all
    serve them; every served token's logprob is the reference's."""
    model, weights = built
    prompts = _prompts([5, 23, 40, 9, 17, 61])
    eng, served = _serve(model, prompts)
    assert eng.megasteps > eng.megasteps_mixed >= 1 and eng.prefill_chunks > 0
    for prompt, (new, lps) in zip(prompts, served):
        table, want = _ref_logprobs(weights, TINY, prompt, new)
        assert np.abs(lps - want).max() < LOGPROB_TOL
        assert (table.argmax(-1) == np.asarray(new)).all()
    # 192 cache layers' worth in miniature: one pool array for keys, one for values
    kc, vc = eng.caches
    assert kc.shape == vc.shape == (12, eng.blocks.num_blocks, 4, 8, 16)


def test_bf16_arithmetic_fails_the_float32_tolerance(built):
    _, weights = built
    low = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), weights)
    model = FAMILY.build_model(dict(TINY, torch_dtype="bfloat16"))
    FAMILY.assign(model, low)
    prompts = _prompts([23, 40])
    _, served = _serve(model.eval(), prompts)
    gaps = [np.abs(lps - _ref_logprobs(weights, TINY, p, new)[1]).max()
            for p, (new, lps) in zip(prompts, served)]
    assert max(gaps) > 10 * LOGPROB_TOL


def test_each_pass_keeps_keys_and_values_of_its_own(built):
    """Cache layer r * depth + l is pass r of layer l: after a prefill every
    one of the twelve holds something, and no two passes of a layer hold the
    same."""
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    eng.add_request(_prompts([20])[0], max_new_tokens=2)
    eng.step()
    kc = np.asarray(eng.caches[0])
    used = np.abs(kc).reshape(12, -1).max(-1)
    assert (used > 0).all()
    for l in range(3):
        for r in range(1, 4):
            assert np.abs(kc[r * 3 + l] - kc[l]).max() > 1e-3


def test_a_call_writes_and_reads_its_own_layer_of_a_stacked_pool():
    """``blha_attention(layer=)`` on a pool ``[layers, nb, KV, bs, D]``: the
    other layers' blocks are neither written nor read (they hold NaN, and
    nothing moves), and the result is what the same call gives on that
    layer's pool alone."""
    H, KV, D, bs, nb, B, LAYERS = 4, 4, 16, 8, 6, 2, 4
    rng = np.random.default_rng(0)
    bt = jnp.asarray([[4, 1, -1], [0, 5, 2]], jnp.int32)
    rope = jnp.asarray(rng.uniform(-1, 1, (2, 1, 24, 1, D // 2)), jnp.float32)
    kw = dict(num_heads=H, kv_num_heads=KV, head_dim=D, block_size=bs,
              use_neox_style=True, rope_emb=rope)

    def feed(pools, now, dec, layer=None):
        n = int(sum(now))
        qkv = jnp.asarray(rng.standard_normal((n, (H + 2 * KV) * D)), jnp.float32)
        cu = jnp.asarray(np.concatenate([[0], np.cumsum(now)]), jnp.int32)
        return qkv, (jnp.zeros(B, jnp.int32), jnp.asarray(dec, jnp.int32),
                     jnp.asarray(now, jnp.int32), cu, bt), dict(max_q_len=max(now), layer=layer)

    alone = (jnp.zeros((nb, KV, bs, D)),) * 2
    stacked = tuple(jnp.full((LAYERS, nb, KV, bs, D), jnp.nan).at[2].set(0.0)
                    for _ in range(2))
    for now, dec in (((11, 17), (0, 0)), ((1, 1), (11, 17)), ((1, 3), (12, 18))):
        qkv, lens, more = feed(alone, now, dec)
        want, *alone = blha_attention(qkv, *alone, *lens, **kw, **more)[:3]
        got, *stacked = blha_attention(qkv, *stacked, *lens, **kw,
                                       **dict(more, layer=jnp.asarray(2)))[:3]
        assert np.array_equal(np.asarray(got), np.asarray(want))
        for one, pool in zip(alone, stacked):
            assert pool.shape == (LAYERS, nb, KV, bs, D)
            assert np.array_equal(np.asarray(pool[2]), np.asarray(one))
            assert np.isnan(np.asarray(pool)[[0, 1, 3]]).all()


@pytest.mark.parametrize("pool,layer", [((6, 4, 8, 16), 2), ((4, 6, 4, 8, 16), None)],
                         ids=["layer_of_a_flat_pool", "stacked_pool_without_layer"])
def test_layer_and_the_pools_form_have_to_agree(pool, layer):
    """``layer=`` says the pool is stacked, not the pool's rank: a layer of a
    flat pool, and a stacked pool with no layer named, are refused in words."""
    H = KV = 4
    kw = dict(num_heads=H, kv_num_heads=KV, head_dim=16, block_size=8, max_q_len=1, layer=layer)
    lens = (jnp.zeros(1, jnp.int32), jnp.asarray([3], jnp.int32), jnp.ones(1, jnp.int32),
            jnp.asarray([0, 1], jnp.int32), jnp.asarray([[1, -1]], jnp.int32))
    with pytest.raises(ValueError, match="stacked pool"):
        blha_attention(jnp.zeros((1, 3 * H * 16)), jnp.zeros(pool), jnp.zeros(pool), *lens, **kw)


def test_a_request_resumed_after_preemption(built):
    model, weights = built
    (prompt,) = _prompts([37], seed=2)
    whole = _serve(model, [prompt], new=20)[1][0][0]
    eng = ServingEngine(model, **ENGINE)
    rid = eng.add_request(prompt, max_new_tokens=20)
    while len(eng._active[rid].generated) < 6 if rid in eng._active else True:
        eng.step()
    req = eng.evict(rid)
    done = list(req.generated)
    assert 6 <= len(done) < 20 and eng.state_summary()["free_slots"] == eng.B
    rid2 = eng.add_request(prompt + done, max_new_tokens=20 - len(done),
                           sampling={"logprobs": True}, sample_offset=len(done))
    rest = eng.run()[rid2]
    assert done + rest == whole
    assert eng.prefix_hit_blocks > 0          # its own blocks, published at eviction
    lps = np.asarray(eng.pop_token_logprobs()[rid2])
    _, want = _ref_logprobs(weights, TINY, prompt + done, rest)
    assert np.abs(lps - want).max() < LOGPROB_TOL


def test_prefix_cache_and_cow_fork_leave_the_tokens_as_they_were(built):
    """A shared block holds all twelve cache layers of its tokens; the COW
    fork copies the block in every one of them."""
    model, _ = built
    (base,) = _prompts([32], seed=4)                 # four whole blocks
    prompts = [base, base + [9, 8, 7], base]          # a hit, and a full match (COW)
    cold = [_serve(model, [p], prefix_cache=False)[1][0][0] for p in prompts]
    eng = ServingEngine(model, **ENGINE)
    warm = []
    for p in prompts:
        rid = eng.add_request(p, max_new_tokens=12)
        warm.append(eng.run()[rid])
    assert warm == cold
    assert eng.prefix_hit_blocks >= 7 and eng._cow_fn is not None


def test_blocks_export_and_import_across_engines(built):
    """The wire header counts CACHE layers (12 here, 192 at the published
    size), and an imported chain serves the tokens the exporter would."""
    model, _ = built
    (base,) = _prompts([32], seed=6)
    src = ServingEngine(model, **ENGINE)
    rid = src.add_request(base, max_new_tokens=4)
    want = src.run()[rid]
    chain = [h for _, h in src._match_cached_prefix(base)]      # parent first
    header, raw = src.export_blocks_packed(chain)
    assert header["layers"] == 12 and header["shape"][:2] == [2, 12]
    assert header["hashes"] == chain and len(chain) == 4
    dst = ServingEngine(model, **ENGINE)
    assert dst.import_blocks_packed(header, raw) == 4
    rid = dst.add_request(base, max_new_tokens=4)
    assert dst.run()[rid] == want and dst.prefix_hit_blocks == 4
    again = ServingEngine(model, **ENGINE)
    assert again.import_blocks(src.export_blocks(header["hashes"])) == 4
    assert np.array_equal(np.asarray(again.caches[0])[:, again.blocks.lookup(header["hashes"][0])],
                          np.asarray(src.caches[0])[:, src.blocks.lookup(header["hashes"][0])])


def test_an_int8_cache_is_refused_with_the_reason(built):
    model, _ = built
    with pytest.raises(ValueError, match="OuroForCausalLM.*loop in the compiled program"):
        ServingEngine(model, cache_quant="int8", **ENGINE)


def test_weight_swap_asks_for_the_same_loop(built):
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    shorter, _ = _build(dict(TINY, total_ut_steps=2))
    with pytest.raises(ValueError, match="geometry"):
        eng.load_weights(shorter)
    again, _ = _build(seed=8)
    assert eng.load_weights(again, version="v1") == "v1"


# ------------------------------------------------- names, spans and counters
# (the layers' scopes lie inside the scan over the stacked weights, whose body
# is traced as a call of its own: the text names them bare, and the device's
# op_name carries the whole path, ``.../loop_pass/while/body/.../mlp``)
SCOPES = ("embed", "loop_pass", "loop_pass/while/body", "norm", "attn_proj",
          "paged_attention", "paged_attention/rope", "paged_attention/kv_write",
          "attn_out", "post_norm", "mlp", "loop_pass/norm", "loop_pass/exit_gate",
          "head", "sample")


@pytest.fixture(scope="module")
def ouro_texts(built):
    return programs.lowered(ServingEngine(built[0], spec_k=2, **ENGINE), debug_info=True)


@pytest.mark.parametrize("kind", ["step", "mega", "mixed", "spec"])
def test_lowered_program_names_the_loops_scopes(ouro_texts, kind):
    text = ouro_texts[kind]
    want = SCOPES + (() if kind == "step" else ("scan_carry",))
    missing = [s for s in want if not re.search(rf'["/(]{s}[/)"]', text)]
    assert not missing, f"{kind}: no operation under {missing}"
    assert f"jit_{'spec_verify' if kind == 'spec' else kind}" in text


@pytest.mark.parametrize("kind", ["step", "mega", "mixed"])
def test_the_program_does_not_grow_with_the_passes_or_the_depth(built, kind):
    """The passes and the layers are loops IN the program: one attention
    call in its text at two passes, at four, and at twice the depth, and a
    text of the same length (a trip count and the pool's leading axis are
    all that differ)."""
    def text_of(**changed):
        model, _ = _build(dict(TINY, **changed))
        return programs.lowered(ServingEngine(model, **ENGINE), False, kinds=(kind,))[kind]

    four = programs.lowered(ServingEngine(built[0], **ENGINE), False, kinds=(kind,))[kind]
    calls = len(re.findall(r"call @blha_attention", four))
    assert calls == 1
    for other in (text_of(total_ut_steps=2), text_of(num_hidden_layers=6)):
        assert len(re.findall(r"call @blha_attention", other)) == calls
        assert other.count("\n") == four.count("\n")
        assert other.count("stablehlo.dot_general") == four.count("stablehlo.dot_general")


def test_loop_counters_are_monotone_and_ride_the_spans(built):
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    harvests = programs.harvests(eng)
    assert (eng.loop_tokens, eng.loop_token_passes) == (0, 0)
    for p in _prompts([20, 9, 41]):
        eng.add_request(p, max_new_tokens=6)
    last = (0, 0)
    while eng._queue or eng._active:
        eng.step()
        now = (eng.loop_tokens, eng.loop_token_passes)
        assert now[0] >= last[0] and now[1] >= last[1]
        last = now
    # every prompt token and every token fed back (five a request) runs four passes
    assert eng.loop_tokens == 70 + 15 and eng.loop_token_passes == 4 * eng.loop_tokens
    assert eng.state_summary()["loop"] == {"passes": 4, "tokens": 85, "token_passes": 340}
    names = {"loop_tokens", "loop_token_passes", "attn_positions_live",
             "attn_positions_read", "attn_rows_kernel", "attn_chunks_kernel",
             "kv_write_tokens", "kv_write_blocks"}
    assert {k for k, _, _ in harvests} >= {"step", "mega", "mixed"}
    assert all(set(h) == names for _, _, h in harvests)
    assert all(l["passes"] == 4 and l["kind"] == k for k, l, _ in harvests)
    assert sum(h["loop_tokens"] for _, _, h in harvests) == 85
    assert sum(h["loop_token_passes"] for _, _, h in harvests) == 340
    # the attention's three count ONE cache layer, as they do for a model of one pass
    assert eng.attn_positions_live == sum(h["attn_positions_live"] for _, _, h in harvests)
    assert 0 < eng.attn_positions_live <= eng.attn_positions_read
    # and the cache write's two: every token fed is written into a cache
    # layer once, by the scatter here (no piece moved)
    assert eng.kv_write_tokens == sum(h["kv_write_tokens"] for _, _, h in harvests) == 85
    assert eng.kv_write_blocks == 0
    assert eng.state_summary()["attention"]["kv_write_tokens"] == 85
    assert eng.state_summary()["moe"] == {"tokens": 0, "local_picks": 0, "rows_grouped": 0}


def test_a_model_of_one_pass_says_so():
    P.seed(0)
    eng = ServingEngine(LlamaForCausalLM(llama_tiny()).eval(), **ENGINE)
    harvests = programs.harvests(eng)
    eng.add_request([3, 17, 101], max_new_tokens=6)
    eng.run()
    assert eng.state_summary()["loop"] == {"passes": 1, "tokens": 0, "token_passes": 0}
    assert harvests and all(l["passes"] == 1 for _, l, _ in harvests)


def test_a_looped_engine_steered_onto_the_chip_writes_its_layer_by_row(monkeypatch):
    """A bf16 looped model with heads of 128 and blocks of 16 is a call both
    kernels admit. With ``on_tpu`` answering yes (the kernels in interpret
    mode) the stacked pool is written piece by piece at the loop's layer:
    the tokens are the scatter's, ``kv_write_tokens`` the same, and
    ``kv_write_blocks`` what the rows' runs lie in."""
    import functools

    from paddle_tpu.inference import serving
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops.pallas import paged_chunk as pc
    from paddle_tpu.ops.pallas import paged_decode as pd
    from paddle_tpu.ops.pallas import paged_write as pw

    cfg = dict(TINY, hidden_size=128, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=1, num_key_value_heads=1, head_dim=128,
               total_ut_steps=2, torch_dtype="bfloat16")
    model, _ = _build(cfg)
    geometry = dict(max_batch_size=2, max_seq_len=64, block_size=16, token_budget=32,
                    megastep_k=4)
    prompts = _prompts([5, 22])

    def run():
        eng = ServingEngine(model, **geometry)
        rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
        out = eng.run()
        return eng, [out[r] for r in rids]

    plain, want = run()
    assert plain.kv_write_blocks == 0 and plain.kv_write_tokens == 27 + 12
    monkeypatch.setattr(serving, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    monkeypatch.setattr(pa, "paged_decode", functools.partial(pd.paged_decode, interpret=True))
    monkeypatch.setattr(pa, "paged_write", functools.partial(pw.paged_write, interpret=True))
    monkeypatch.setattr(pa, "paged_chunk", functools.partial(pc.paged_chunk, interpret=True))
    pa.blha_attention.clear_cache()
    try:
        eng, got = run()
    finally:
        pa.blha_attention.clear_cache()
    assert got == want
    assert eng.kv_write_tokens == plain.kv_write_tokens
    # the prompts lie in one piece of 16 positions and in two; a token fed
    # back in one
    assert eng.kv_write_blocks == 1 + 2 + 12
    assert eng.attn_rows_kernel == 12
    # the prefill step's two chunk rows, ONE cache layer's: the stacked pool's layer is
    # block numbers moved before the call, so ``paged_chunk`` rides it unchanged
    assert eng.attn_chunks_kernel == 2 and plain.attn_chunks_kernel == 0
