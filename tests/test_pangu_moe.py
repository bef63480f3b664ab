"""openPangu-Ultra-MoE at a tiny size on the CPU, seeded random weights: the
model's own ``forward``, the serving engine's trunk over the paged latent
cache (prefill, decode, a request resumed after preemption, the prefix cache
and its COW fork, n-gram speculation), the expert layer's share of a
deployment, the typed refusals, the names in the compiled programs, the
counters; all held to the plain float32 reference
(benchmark/references/mla_moe.py), which shares nothing with the program."""
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine, ServingFrontend
from paddle_tpu.inference.serving import SamplingParams
from paddle_tpu.models import (LlamaForCausalLM, PanguUltraMoEConfig,
                               PanguUltraMoEForCausalLM, llama_tiny)
from paddle_tpu.models import pangu_moe
from paddle_tpu.ops.held_experts import _swiglu, held_experts
from paddle_tpu.ops.latent_attention import latent_attention, rope_half

from benchmark.harness import loader

import programs
from programs import ENGINE

ROOT = loader.ROOT
FAMILY = loader.load_module("families", "mla_moe")
REFERENCE = loader.load_module("references", "mla_moe")
TINY = programs.TINY["pangu"]

# A float32 engine and the float32 reference differ by the order of their
# sums alone: the absorbed scores against the expanded ones, a blocked
# softmax, experts added tile by tile.  1e-4 nats is twenty times what that
# gives here and a fiftieth of what bf16 arithmetic gives (0.005-0.05: its 8
# mantissa bits against 24), so bf16 in a float32 configuration fails it.
LOGPROB_TOL = 1e-4


@pytest.fixture(autouse=True)
def _no_fleet_group():
    set_hybrid_communicate_group(None)


def _build(cfg=TINY, seed=7):
    return programs.build("pangu", cfg, seed)


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
    lp = lg - np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1, keepdims=True)) \
        - lg.max(-1, keepdims=True)
    return lp, lp[np.arange(len(new)), new]


def _serve(model, prompts, new=12, **engine):
    eng = ServingEngine(model, **{**ENGINE, **engine})
    rids = [eng.add_request(p, max_new_tokens=new, sampling={"logprobs": True})
            for p in prompts]
    out = eng.run()
    lps = eng.pop_token_logprobs()
    return eng, [(out[r], np.asarray(lps[r])) for r in rids]


# ------------------------------------------------------------- the model
def test_config_keeps_the_published_names_and_refuses_another_model():
    cfg = PanguUltraMoEConfig()
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.n_routed_experts) == (7680, 61, 256)
    assert cfg.experts_held == (0, 256) and cfg.latent_width == 576
    assert cfg.latent_cache_width == 640      # whole 128-lane tiles
    assert pangu_moe.pangu_ultra_moe_tiny().latent_cache_width == 24
    with pytest.raises(ValueError, match="no range"):
        PanguUltraMoEConfig(experts_held=(250, 260))
    with pytest.raises(ValueError, match="sandwich"):
        PanguUltraMoEConfig(sandwich_norm=False)


def test_forward_agrees_with_the_reference(built):
    model, weights = built
    ids = np.asarray(_prompts([40])[0], np.int32)
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(40)))
    got = np.asarray(model(P.to_tensor(ids[None]))._value)[0]
    assert np.abs(got - want).max() < 2e-5
    low = np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(40), quant="int8"))
    assert 1e-3 < np.abs(low - want).max()          # the control moves them
    with pytest.raises(ValueError):
        REFERENCE.logits_at(weights, TINY, ids, np.arange(40), quant="int3")


def test_the_next_token_module_agrees_with_the_reference():
    cfg = dict(TINY, num_nextn_predict_layers=1)
    model, weights = _build(cfg, seed=3)
    assert "mtp" in weights and model.mtp is not None
    ids = np.asarray(_prompts([24], seed=5)[0], np.int32)
    logits, mtp = model(P.to_tensor(ids[None]), mtp=True)
    want = np.asarray(REFERENCE.mtp_logits_at(weights, cfg, ids, np.arange(23)))
    assert np.asarray(mtp._value).shape == (1, 23, cfg["vocab_size"])
    assert np.abs(np.asarray(mtp._value)[0] - want).max() < 2e-5
    main = np.asarray(REFERENCE.logits_at(weights, cfg, ids, np.arange(24)))
    assert np.abs(np.asarray(logits._value)[0] - main).max() < 2e-5
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        _build()[0](P.to_tensor(ids[None]), mtp=True)


def test_lazy_guard_makes_abstract_parameters():
    with P.LazyGuard():
        model = PanguUltraMoEForCausalLM(FAMILY.model_config(TINY))
    p = model.lm_head.weight
    assert isinstance(p._value, jax.ShapeDtypeStruct) and tuple(p.shape) == (64, 256)
    eager = PanguUltraMoEForCausalLM(FAMILY.model_config(TINY))
    assert isinstance(eager.lm_head.weight._value, jax.Array)
    with pytest.raises(ValueError, match="weight"):
        FAMILY.assign(model, FAMILY.make_weights(dict(TINY, hidden_size=32,
                                                      num_attention_heads=2), 1))


# -------------------------------------------------- engine against reference
def test_prefill_then_decode_through_the_latent_cache(built):
    """Prompts shorter and longer than a launch's budget, so the single-step
    prefill, the mixed scan and the decode scan all serve them."""
    model, weights = built
    prompts = _prompts([5, 23, 40, 9, 17, 61])
    eng, served = _serve(model, prompts)
    assert eng.megasteps > eng.megasteps_mixed >= 1
    for prompt, (new, lps) in zip(prompts, served):
        table, want = _ref_logprobs(weights, TINY, prompt, new)
        assert np.abs(lps - want).max() < LOGPROB_TOL
        assert (table.argmax(-1) == np.asarray(new)).all()


def test_bf16_arithmetic_fails_the_float32_tolerance(built):
    _, weights = built
    low = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), weights)
    cfg = dict(TINY, torch_dtype="bfloat16")
    model = FAMILY.build_model(cfg)
    FAMILY.assign(model, low)
    prompts = _prompts([23, 40])
    _, served = _serve(model.eval(), prompts)
    gaps = [np.abs(lps - _ref_logprobs(weights, TINY, p, new)[1]).max()
            for p, (new, lps) in zip(prompts, served)]
    assert max(gaps) > 10 * LOGPROB_TOL


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
    assert eng.prefix_hit_blocks >= 4 + 4 and eng._cow_fn is not None


def test_ngram_speculation_commits_the_same_tokens(built):
    model, _ = built
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    plain = _serve(model, [prompt], new=40)[1][0][0]
    eng, served = _serve(model, [prompt], new=40, spec_k=2)
    assert served[0][0] == plain and eng.spec_verify_forwards > 0


def test_served_behind_the_frontend(built):
    model, _ = built
    prompts = _prompts([12, 30, 7])
    plain = [toks for toks, _ in _serve(model, prompts, new=10)[1]]
    fe = ServingFrontend([ServingEngine(model, **ENGINE)])
    rids = [fe.submit(p, max_new_tokens=10) for p in prompts]
    while fe.pending:
        fe.step()
    assert [fe.result(r).tokens for r in rids] == plain


# ------------------------------------------------------------- attention
def test_absorbed_attention_equals_the_expanded_form():
    """``latent_attention`` (queries carried into the latent space, scores
    against the paged entries, blocked online softmax) against keys and
    values expanded a head over each row's whole context: one decode row, a
    row feeding a chunk behind a cached prefix, a one-token chunk, and an
    empty slot."""
    rng = np.random.default_rng(0)
    H, N, R, V, C, bs, P_, B = 4, 8, 8, 8, 16, 4, 6, 4
    dec = np.asarray([13, 5, 0, 0], np.int32)
    now = np.asarray([1, 7, 1, 0], np.int32)
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    T = 12                                              # 9 packed, 3 of padding
    wkv_b = rng.normal(size=(C, H, N + V)).astype(np.float32) * C ** -0.5
    ctx = [rng.normal(size=(int(d + n), C + R)).astype(np.float32) for d, n in zip(dec, now)]
    bt = np.full((B, P_), -1, np.int32)
    cache = np.zeros((B * P_ + 1, bs, C + R), np.float32)
    order = rng.permutation(B * P_)
    for b in range(B):
        bt[b] = order[b * P_:(b + 1) * P_]
        for pos in range(int(dec[b])):                 # what earlier steps cached
            cache[bt[b, pos // bs], pos % bs] = ctx[b][pos]
    q_n = rng.normal(size=(T, H, N)).astype(np.float32)
    q_r = rng.normal(size=(T, H, R)).astype(np.float32)
    entries = np.zeros((T, C + R), np.float32)
    for b in range(B):
        entries[cu[b]:cu[b + 1]] = ctx[b][dec[b]:]
    q_lat = np.einsum("thn,chn->thc", q_n, wkv_b[..., :N])
    scale = (N + R) ** -0.5
    for mq in (8, 12):
        o_lat, new_cache = latent_attention(
            jnp.asarray(np.concatenate([q_lat, q_r], -1)), jnp.asarray(entries),
            jnp.asarray(cache), jnp.asarray(dec), jnp.asarray(now), jnp.asarray(cu),
            jnp.asarray(bt), rank=C, max_q_len=mq, scale=scale, ctx_block=8)
        got = np.einsum("thc,chv->thv", np.asarray(o_lat), wkv_b[..., N:])
        for b in range(B):
            for j in range(int(now[b])):
                t, n = cu[b] + j, int(dec[b]) + j + 1
                k = np.einsum("lc,chn->lhn", ctx[b][:n, :C], wkv_b[..., :N])
                v = np.einsum("lc,chv->lhv", ctx[b][:n, :C], wkv_b[..., N:])
                s = (np.einsum("hn,lhn->hl", q_n[t], k)
                     + np.einsum("hr,lr->hl", q_r[t], ctx[b][:n, C:])) * scale
                p = np.exp(s - s.max(-1, keepdims=True))
                want = np.einsum("hl,lhv->hv", p / p.sum(-1, keepdims=True), v)
                assert np.abs(got[t] - want).max() < 1e-5, (mq, b, j)
            pos = int(dec[b]) + int(now[b]) - 1
            if now[b]:
                assert (np.asarray(new_cache)[bt[b, pos // bs], pos % bs] == ctx[b][pos]).all()
        assert (np.asarray(o_lat)[cu[-1]:] == 0).all()      # padding stays empty
    one_only = latent_attention(
        jnp.asarray(np.concatenate([q_lat, q_r], -1))[:4], jnp.asarray(entries)[:4],
        jnp.asarray(cache), jnp.asarray(dec), jnp.asarray(np.asarray([1, 0, 1, 0], np.int32)),
        jnp.asarray(np.asarray([0, 1, 1, 2, 2], np.int32)), jnp.asarray(bt),
        rank=C, max_q_len=1, scale=scale, ctx_block=8)[0]
    assert np.abs(np.asarray(one_only)[0] - np.asarray(o_lat)[0]).max() < 1e-6


def test_rope_half_rotates_the_two_halves():
    x = np.arange(8, dtype=np.float32).reshape(1, 1, 8)
    ang = np.asarray([[0.1, 0.2, 0.3, 0.4]], np.float32)
    out = np.asarray(rope_half(jnp.asarray(x), jnp.cos(ang), jnp.sin(ang)))[0, 0]
    x1, x2 = x[0, 0, :4], x[0, 0, 4:]
    want = np.concatenate([x1 * np.cos(ang[0]) - x2 * np.sin(ang[0]),
                           x2 * np.cos(ang[0]) + x1 * np.sin(ang[0])])
    assert np.abs(out - want).max() < 1e-6


# ------------------------------------------------------- the expert layer
def test_the_share_adds_up():
    """The routed parts that the four shares of a 16-expert layer give, with
    the shared expert counted once, equal the uncut reference's expert
    layer: in the program (``held_experts``) and in the reference alike."""
    uncut = dict(TINY, n_routed_experts=16, experts_held=[0, 16])
    weights = FAMILY.make_weights(uncut, 13)
    p = weights["layers"][1]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(50, 64)), jnp.float32)
    idx, w = REFERENCE.route(x, p["router"], 4, 2.5)
    shared = REFERENCE.swiglu(x, p["sg"], p["su"], p["sd"])
    whole = shared + sum(
        REFERENCE.weight_of(idx, w, e)[:, None] * REFERENCE.swiglu(
            x, p["eg"][e], p["eu"][e], p["ed"][e]) for e in range(16))
    parts, picks = [], 0
    for lo in (0, 4, 8, 12):
        pidx, pw = pangu_moe.route(x, p["router"], 4, 2.5)
        y, n = held_experts(x, pidx, pw, p["eg"][lo:lo + 4], p["eu"][lo:lo + 4],
                                      p["ed"][lo:lo + 4], lo, tile=8)
        parts.append(np.asarray(y))
        picks += int(n)
    assert picks == 50 * 4                      # every pick falls on exactly one share
    assert np.abs(np.asarray(shared) + sum(parts) - np.asarray(whole)).max() < 2e-5
    assert np.abs(parts[0]).max() > 0.01        # and a share is not nothing


def test_no_pick_is_dropped_when_every_token_picks_one_expert():
    """No capacity: 40 tokens that all pick the same held expert all get it."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    eg, eu = (jnp.asarray(rng.normal(size=(2, 16, 8)), jnp.float32) for _ in range(2))
    ed = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    idx = jnp.full((40, 1), 5, jnp.int32)
    w = jnp.full((40, 1), 0.5, jnp.float32)
    y, picks = held_experts(x, idx, w, eg, eu, ed, 4, tile=16)
    want = 0.5 * np.asarray(_swiglu(x, eg[1], eu[1], ed[1]))
    assert int(picks) == 40 and np.abs(np.asarray(y) - want).max() < 1e-5
    none, picks = held_experts(x, idx, w, eg, eu, ed, 8, tile=16)
    assert int(picks) == 0 and not np.asarray(none).any()


# ------------------------------------------------------------ the refusals
def test_int8_cache_and_block_transfer_refuse_a_latent_cache(built):
    model, _ = built
    with pytest.raises(ValueError, match="latent"):
        ServingEngine(model, cache_quant="int8", **ENGINE)
    eng = ServingEngine(model, **ENGINE)
    for call in (lambda: eng.export_blocks(["h"]), lambda: eng.export_blocks_packed(["h"]),
                 lambda: eng.import_blocks({}), lambda: eng.import_blocks_packed({}, b"")):
        with pytest.raises(ValueError, match="latent"):
            call()


def test_load_weights_refuses_another_geometry(built):
    model, weights = built
    eng = ServingEngine(model, **ENGINE)
    other, _ = _build(dict(TINY, experts_held=[0, 4]))
    with pytest.raises(ValueError, match="geometry"):
        eng.load_weights(other)
    again, _ = _build(seed=8)
    assert eng.load_weights(again, version="v1") == "v1"
    set_hybrid_communicate_group(None)
    with pytest.raises(ValueError, match="geometry"):
        eng.load_weights(LlamaForCausalLM(llama_tiny()))


# ------------------------------------------------- names, spans and counters
# (regular expressions: the three scopes inside the loops over the context
# lie under ``latent_attention/while/body/``, once for each loop around them)
IN_LOOP = "latent_attention/(?:while/body/)+"
SCOPES = ("embed", "norm", "latent_proj", "latent_attention", "latent_attention/kv_write",
          IN_LOOP + "kv_gather", IN_LOOP + "scores", IN_LOOP + "values", "attn_out",
          "post_norm", "router", "experts", "experts/while/body", "shared_expert", "mlp",
          "head", "sample")


@pytest.fixture(scope="module")
def pangu_texts(built):
    return programs.lowered(ServingEngine(built[0], spec_k=2, **ENGINE), debug_info=True)


@pytest.mark.parametrize("kind", ["step", "mega", "mixed", "spec"])
def test_lowered_program_names_the_new_scopes(pangu_texts, kind):
    text = pangu_texts[kind]
    want = SCOPES + (() if kind == "step" else ("scan_carry",))
    missing = [s for s in want if not re.search(rf'["/(]{s}[/)"]', text)]
    assert not missing, f"{kind}: no operation under {missing}"
    assert f"jit_{'spec_verify' if kind == 'spec' else kind}" in text


# The Llama programs read the cache a context block at a time (ISSUE 27): no
# value in a program's lowered text has the shape of a WHOLE gathered table
# (``B x KV x P*bs x D``, or its blocks before they are merged) or of scores
# padded to it (``... x mq x P*bs``), in any type. The guard's geometry gives
# a table of 128 blocks of 8, twice what one pass over the context reads, and
# a batch of 3, which no other axis of the model has.
LLAMA_GEOMETRY = dict(max_batch_size=4, max_seq_len=64, block_size=8, token_budget=16,
                      megastep_k=4, spec_k=2)
GUARD_GEOMETRY = dict(LLAMA_GEOMETRY, max_batch_size=3, max_seq_len=1024)


@pytest.fixture(scope="module")
def llama_texts():
    set_hybrid_communicate_group(None)
    P.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    model.eval()
    eng = ServingEngine(model, **GUARD_GEOMETRY)
    return eng, programs.lowered(eng, debug_info=False)


def _whole_table_shapes(text, B, P, bs, KV, D):
    """The tensor types of ``text`` that hold every position of every row's
    table: ``[B, ..., P*bs, D]`` / ``[B, P, KV, bs, D]`` in any order of the
    middle axes, or ``[B, ..., P*bs]`` with heads or queries between."""
    L = P * bs
    found = set()
    for dims in set(re.findall(r"tensor<((?:\d+x)+)[a-z]+\d*>", text)):
        shape = [int(d) for d in dims.split("x") if d]
        n = int(np.prod(shape))
        if len(shape) < 3 or shape[0] != B:
            continue
        if n % (B * L * D) == 0 and shape[-1] == D and (L in shape or (P in shape and bs in shape)):
            found.add(dims)             # gathered keys or values
        if shape[-1] == L and n >= B * KV * L:
            found.add(dims)             # scores, probabilities or a mask over the table
    return found


@pytest.mark.parametrize("kind", ["step", "mega", "mixed", "spec"])
def test_llama_programs_hold_no_whole_table(llama_texts, kind):
    eng, texts = llama_texts
    cfg = llama_tiny()
    KV, D = cfg.num_key_value_heads, cfg.head_dim
    assert eng.P * eng.bs == 1024 and eng.B not in (KV, cfg.num_attention_heads)
    # the guard sees the form it guards against: the plain gather of a table
    gathered = jax.jit(lambda c, t: c[t]).lower(
        jax.ShapeDtypeStruct(eng.key_caches[0].shape, jnp.float32),
        jax.ShapeDtypeStruct((eng.B, eng.P), jnp.int32)).as_text()
    assert _whole_table_shapes(gathered, eng.B, eng.P, eng.bs, KV, D)
    assert not _whole_table_shapes(texts[kind], eng.B, eng.P, eng.bs, KV, D)


def _harvests(eng):
    """[(kind of the launch, the attributes of its ``engine.harvest`` span)],
    filled as the engine runs."""
    seen, kinds = [], []
    launch, phase = eng._launch_phase, eng._phase

    def launched(kind, *a, **kw):
        kinds.append(kind)
        return launch(kind, *a, **kw)

    def entered(name, **attrs):
        if name == "harvest":
            seen.append((kinds[-1], attrs))
        return phase(name, **attrs)

    eng._launch_phase, eng._phase = launched, entered
    return seen


def test_expert_counters_are_monotone_and_ride_the_harvest_span(built):
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    harvests = _harvests(eng)
    before = (eng.moe_tokens, eng.moe_local_picks)
    assert before == (0, 0)
    for p in _prompts([20, 9]):
        eng.add_request(p, max_new_tokens=6)
    last = before
    while eng._queue or eng._active:
        eng.step()
        now = (eng.moe_tokens, eng.moe_local_picks)
        assert now[0] >= last[0] and now[1] >= last[1]
        last = now
    # two expert layers see every token fed: 29 prompt tokens and 5 + 5 fed back
    assert eng.moe_tokens == 2 * (29 + 10)
    assert 0 < eng.moe_local_picks < eng.moe_tokens * TINY["num_experts_per_tok"]
    # on the CPU every expert layer takes the tile loop: nothing was grouped
    assert eng.state_summary()["moe"] == {"tokens": eng.moe_tokens,
                                          "local_picks": eng.moe_local_picks,
                                          "rows_grouped": 0}
    # ... and nothing of the dense attention's, which this trunk does not run
    seen = [a for _, a in harvests]
    assert seen and all(set(a) == {"moe_tokens", "moe_local_picks", "expert_rows_grouped",
                                   "latent_rows_kernel", "latent_chunks_kernel"}
                        for a in seen)
    # the CPU: every row of the latent cache took the XLA loops
    assert eng.state_summary()["latent_attention"] == {"rows_kernel": 0, "chunks_kernel": 0}
    assert eng.state_summary()["attention"] == {"positions_live": 0, "positions_read": 0,
                                                "rows_kernel": 0, "chunks_kernel": 0,
                                                "kv_write_tokens": 0, "kv_write_blocks": 0}
    assert sum(a["moe_tokens"] for a in seen) == eng.moe_tokens
    assert sum(a["moe_local_picks"] for a in seen) == eng.moe_local_picks


def test_attention_positions_of_a_two_row_example():
    """Blocks of 8 in a table of 80: a pass reads 512 positions, a tile 8 rows."""
    from paddle_tpu.ops.paged_attention import attention_positions

    def count(rows):
        dec, now = (jnp.asarray(x, jnp.int32) for x in zip(*rows))
        live, read, in_kernel = (int(n) for n in attention_positions(
            dec, now, block_size=8, blocks_per_seq=80))
        assert in_kernel == 0              # the XLA pass (the kernel's count:
        return live, read                  # tests/test_paged_attention.py)

    # two decoding rows in one tile: the longer one's two passes for all 8 rows
    assert count([(600, 1), (10, 1)]) == (601 + 11, 2 * 512 * 8 + 2)
    # a chunk row reads its own two passes; the tile of the other row one pass
    assert count([(520, 4), (3, 1)]) == (524 + 4, 2 * 512 + 512 * 8 + 5)
    # an empty slot and a prompt's first chunk read nothing of the cache
    assert count([(0, 0), (0, 7)]) == (7, 7)


def test_attention_counters_are_monotone_and_ride_the_harvest_span():
    set_hybrid_communicate_group(None)
    model = LlamaForCausalLM(llama_tiny())
    eng = ServingEngine(model.eval(), **dict(LLAMA_GEOMETRY, megastep_k=1, spec_k=0))
    harvests = _harvests(eng)
    assert (eng.attn_positions_live, eng.attn_positions_read) == (0, 0)
    eng.add_request([5, 6, 7], max_new_tokens=2)
    eng.add_request([8, 9, 10, 11, 12], max_new_tokens=2)
    last = (0, 0)
    while eng._queue or eng._active:
        eng.step()
        now = (eng.attn_positions_live, eng.attn_positions_read)
        assert now[0] >= last[0] and now[1] >= last[1]
        last = now
    # both prompts in one step (3 + 5 positions, nothing cached yet), then both
    # rows decode at contexts 3 and 5: one pass (the whole table of 64) for the
    # tile's 8 rows, and the two tokens fed
    assert eng.attn_positions_live == (3 + 5) + (4 + 6)
    assert eng.attn_positions_read == (3 + 5) + (64 * 8 + 2)
    assert eng.attn_positions_read >= eng.attn_positions_live > 0
    assert eng.state_summary()["attention"] == {
        "positions_live": eng.attn_positions_live,
        "positions_read": eng.attn_positions_read,
        "rows_kernel": 0,                  # the CPU: every row took the XLA pass
        "chunks_kernel": 0,
        "kv_write_tokens": 3 + 5 + 2,      # ... and the scatter wrote every token fed
        "kv_write_blocks": 0}
    seen = [a for _, a in harvests]
    assert len(seen) == 2
    assert all(set(a) == {"attn_positions_live", "attn_positions_read", "attn_rows_kernel",
                          "attn_chunks_kernel", "kv_write_tokens", "kv_write_blocks"}
               for a in seen)
    assert eng.attn_rows_kernel == sum(a["attn_rows_kernel"] for a in seen) == 0
    assert sum(a["attn_positions_live"] for a in seen) == eng.attn_positions_live
    assert sum(a["attn_positions_read"] for a in seen) == eng.attn_positions_read


@pytest.fixture(scope="module")
def llama_harvests():
    """The harvest spans of a Llama engine driven through its four programs:
    a prefill step, a prompt arriving beside a decoding row (mixed scan), the
    decode scan, and a repetitive prompt that drafts (verification)."""
    set_hybrid_communicate_group(None)
    model = LlamaForCausalLM(llama_tiny())
    eng = ServingEngine(model.eval(), **LLAMA_GEOMETRY)
    harvests = _harvests(eng)
    eng.add_request([3, 17, 101], max_new_tokens=12, sampling=SamplingParams(spec=False))
    eng.step()
    eng.add_request(list(range(40, 50)), max_new_tokens=6, sampling=SamplingParams(spec=False))
    eng.run()
    eng.add_request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=48)
    eng.run()
    return harvests


@pytest.mark.parametrize("kind", ["step", "mega", "mixed", "spec"])
def test_every_llama_program_counts_attention_positions(llama_harvests, kind):
    """Each of the four programs adds to both counters (a scan sums its
    iterations), and reads at least what is live."""
    seen = [a for k, a in llama_harvests if k == kind]
    assert seen, f"no {kind} launch among {sorted({k for k, _ in llama_harvests})}"
    for a in seen:
        assert a["attn_positions_read"] >= a["attn_positions_live"] > 0


def test_a_dense_model_counts_no_experts():
    set_hybrid_communicate_group(None)
    model = LlamaForCausalLM(llama_tiny())
    eng = ServingEngine(model.eval(), **LLAMA_GEOMETRY)
    eng.add_request([5, 6, 7], max_new_tokens=6)
    eng.run()
    assert eng.state_summary()["moe"] == {"tokens": 0, "local_picks": 0, "rows_grouped": 0}
    assert [name for name, _ in eng.cache_spec.arrays] == ["k", "v"]
    assert len(eng.caches) == 2 and eng.key_caches is eng.caches[0]


# ------------------------------------------------ the configuration's file
# openPangu-Ultra-MoE-718B's row of the model-configs catalog (``config`` of
# /opt/skills/guides/model-configs/architectures.jsonl), as published at
# huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B config.json
CATALOG_CONFIG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
           "vocab_size": 19200, "num_nextn_predict_layers": 0}


def _config_file():
    with open(os.path.join(ROOT, "benchmark/configs/openpangu-ultra-moe-718b.serve1.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG_CONFIG))
def test_the_configuration_file_holds_the_catalog_row(key):
    cfg = _config_file()
    assert cfg["published"][key] == CATALOG_CONFIG[key]
    if key in REDUCED:
        assert cfg[key] == REDUCED[key] and key in cfg["reduced"]
    else:
        assert cfg[key] == CATALOG_CONFIG[key] and key not in cfg["reduced"]


def test_the_configuration_file_states_its_share_and_builds():
    cfg = _config_file()
    assert set(cfg["published"]) == set(CATALOG_CONFIG) and set(cfg["reduced"]) == set(REDUCED)
    assert cfg["router_outputs"] == 256 and cfg["experts_held"] == [0, 16]
    assert "16 chips" in cfg["stands_for"] and cfg["family"] == "mla_moe"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):                      # the guide's own copy, where present
        rows = [json.loads(line) for line in open(catalog)]
        (row,) = [r for r in rows if r["name"] == "openPangu-Ultra-MoE-718B"]
        assert row["config"] == cfg["published"] and cfg["source"] == row["source_url"]
    mc = FAMILY.model_config(cfg)
    assert (mc.n_routed_experts, mc.experts_held, mc.num_hidden_layers) == (256, (0, 16), 5)
    dense, sparse, outer = FAMILY.leaf_shapes(cfg)
    count = lambda shapes: sum(int(np.prod(s)) for s in shapes.values())   # noqa: E731
    total = count(dense) + 4 * count(sparse) + count(outer)
    assert abs(total / 4.919e9 - 1) < 0.001          # the issue's arithmetic
    assert sparse["eg"] == (16, 7680, 2048) and sparse["router"] == (7680, 256)
