"""DeepSeek-V3.2-Exp (latent attention under a LEARNED selection, group-limited
routing with a selection bias, YaRN) at a tiny size on the CPU, seeded random
weights: the model's own ``forward``, the serving engine's trunk over a paged
pool of TWO arrays a layer (``latent`` and ``index_k``: prefill, the mixed
scan, the decode scan, a request resumed after preemption, the prefix cache
and its COW fork), the exact selection with its ties, the routing, the expert
layer's share of a deployment, the YaRN table, the typed refusals, the names
in the compiled programs, the counters; all held to the plain float32
reference (benchmark/references/mla_dsa_moe.py), which shares nothing with
the program."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine, ServingFrontend
from paddle_tpu.models import (DeepseekV32Config, DeepseekV32ForCausalLM, LlamaForCausalLM,
                               deepseek_v32, deepseek_v32_tiny, llama_tiny, pangu_moe)
from paddle_tpu.ops.held_experts import held_experts
from paddle_tpu.ops.latent_attention import (Selection, latent_attention, selection_reads,
                                               token_coords)
from paddle_tpu.ops.sparse_index import index_scores, select_topk, sparse_index

from benchmark.harness import loader

import programs
from programs import ENGINE

FAMILY = loader.load_module("families", "mla_dsa_moe")
REFERENCE = loader.load_module("references", "mla_dsa_moe")
TINY = programs.TINY["deepseek"]

# A float32 engine and the float32 reference differ by the order of their
# sums alone: the absorbed scores against the expanded ones, a blocked softmax
# against a whole one, the indexer's dots blocked over the context, experts
# added tile by tile: 2e-6 nats here.  1e-4 is fifty times that and a fiftieth
# of what bf16 arithmetic gives (0.005-0.05: its 8 mantissa bits against 24,
# and a selection that flips at the threshold), so bf16 in a float32
# configuration fails it.  An index score within 1e-7 of the 8th largest
# could flip a selection in float32 too; no prompt here has one.
LOGPROB_TOL = 1e-4


@pytest.fixture(autouse=True)
def _no_fleet_group():
    set_hybrid_communicate_group(None)


def _build(cfg=TINY, seed=7):
    return programs.build("deepseek", cfg, seed)


@pytest.fixture(scope="module")
def built():
    set_hybrid_communicate_group(None)
    return _build()


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


# ------------------------------------------------------------- the model
def test_config_keeps_the_published_names_and_refuses_another_model():
    cfg = DeepseekV32Config()
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.n_routed_experts) == (7168, 61, 256)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (64, 128, 2048)
    assert (cfg.n_group, cfg.topk_group, cfg.rms_norm_eps) == (8, 4, 1e-6)
    assert cfg.experts_held == (0, 256) and cfg.latent_width == 576
    assert cfg.latent_cache_width == 640      # whole 128-lane tiles
    assert abs(cfg.mscale - 1.3689) < 1e-4
    assert abs(cfg.softmax_scale - 192 ** -0.5 * cfg.mscale ** 2) < 1e-12
    assert DeepseekV32Config(rope_scaling=None).softmax_scale == 192 ** -0.5
    assert deepseek_v32_tiny().index_topk == 8
    with pytest.raises(ValueError, match="no range"):
        DeepseekV32Config(experts_held=(250, 260))
    with pytest.raises(ValueError, match="noaux_tc"):
        DeepseekV32Config(topk_method="greedy")
    with pytest.raises(ValueError, match="groups"):
        DeepseekV32Config(n_group=7)
    with pytest.raises(ValueError, match="YaRN"):
        DeepseekV32Config(rope_scaling={"type": "linear", "factor": 2})


def test_the_functions_shared_with_the_other_latent_model_have_one_definition():
    for name in ("_latent_proj", "_moe_ffn", "_q_latent", "_rms", "_swiglu"):
        assert getattr(deepseek_v32, name) is getattr(pangu_moe, name), name
    assert deepseek_v32.latent_attention is latent_attention
    import inspect

    src = inspect.getsource(deepseek_v32)
    assert "def held_experts" not in src and "def _latent_proj" not in src


def test_the_bf16_witness_lies_between_float32_and_the_controls(built):
    """``quant="bf16"``: the reference with its matmuls and what a cache would
    store rounded to bfloat16 moves the logits, by less than W8A8 does."""
    _, weights = built
    ids = np.asarray(_prompts([60])[0], np.int32)
    want, bf16, int8 = (np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(60), quant=q))
                        for q in (None, "bf16", "int8"))
    assert 1e-3 < np.abs(bf16 - want).mean() < np.abs(int8 - want).mean()


@pytest.mark.parametrize("quant", [None, "int8", "recent", "dense"])
def test_forward_agrees_with_the_reference_and_each_control_moves_it(built, quant):
    model, weights = built
    ids = np.asarray(_prompts([40])[0], np.int32)
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(40)))
    if quant is None:
        got = np.asarray(model(P.to_tensor(ids[None]))._value)[0]
        assert np.abs(got - want).max() < 2e-5
        with pytest.raises(ValueError):
            REFERENCE.logits_at(weights, TINY, ids, np.arange(40), quant="int3")
        return
    low = np.asarray(REFERENCE.logits_at(weights, TINY, ids, np.arange(40), quant=quant))
    # the first index_topk positions see all of their context under every mode
    k = TINY["index_topk"]
    if quant != "int8":
        assert np.abs(low[:k] - want[:k]).max() < 2e-5
    assert np.abs(low[k:] - want[k:]).max() > 0.05          # the control moves the rest


def test_the_next_token_module_agrees_with_the_reference():
    cfg = dict(TINY, num_nextn_predict_layers=1)
    model, weights = _build(cfg, seed=3)
    assert "mtp" in weights and model.mtp is not None
    assert "wiq" in weights["mtp"]["layer"]             # the module's layer has an indexer
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
        model = DeepseekV32ForCausalLM(FAMILY.model_config(TINY))
    p = model.lm_head.weight
    assert isinstance(p._value, jax.ShapeDtypeStruct) and tuple(p.shape) == (64, 256)
    bias = model.model.layers[1].mlp.gate.e_score_correction_bias
    assert tuple(bias.shape) == (16,) and jnp.dtype(bias._value.dtype) == jnp.float32
    with pytest.raises(ValueError, match="weight"):
        FAMILY.assign(model, FAMILY.make_weights(dict(TINY, hidden_size=32,
                                                      num_attention_heads=2), 1))


# ------------------------------------------------------------ YaRN
def test_the_yarn_table_is_the_published_one():
    """inv'_i = inv_i / factor * ramp_i + inv_i (1 - ramp_i) with the ramp
    between the correction dimensions of beta_fast and beta_slow, written out
    here a second time; at the published numbers the range is [10, 23]."""
    cfg = DeepseekV32Config()
    r, theta, rs = 64, 10000.0, cfg.rope_scaling
    dim = lambda beta: r * math.log(4096 / (beta * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
    lo, hi = math.floor(dim(32)), math.ceil(dim(1))
    assert (lo, hi) == (10, 23)
    i = np.arange(32)
    inv = theta ** (-2.0 * i / r)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    inv = inv / rs["factor"] * ramp + inv * (1 - ramp)
    table = np.asarray(deepseek_v32.rope_table(cfg, 300))
    assert table.shape == (2, 300, 32)
    pos = np.arange(300)[:, None]
    assert np.abs(table[0] - np.cos(pos * inv)).max() < 1e-5
    assert np.abs(table[1] - np.sin(pos * inv)).max() < 1e-5
    # the fast dimensions are untouched, the slow ones turn 40 times slower
    plain = np.asarray(deepseek_v32.rope_table(DeepseekV32Config(rope_scaling=None), 300))
    assert np.array_equal(table[:, :, :10], plain[:, :, :10])
    assert np.abs(table[1, 40, 31] - plain[1, 1, 31]).max() < 1e-6
    ref = np.asarray(REFERENCE.yarn_inv_freq(r, theta, rs))
    assert np.abs(ref - inv).max() < 1e-7
    assert abs(REFERENCE.mscale(rs, "mscale_all_dim") - cfg.mscale) < 1e-12


# ------------------------------------------------------- the selection
CASES = {
    "random": lambda rng, L: rng.standard_normal((16, L)),
    "many_ties": lambda rng, L: np.round(rng.standard_normal((16, L)) * 2) / 2,
    "all_equal": lambda rng, L: np.zeros((16, L)),
    "relu_zeros": lambda rng, L: np.maximum(rng.standard_normal((16, L)), 0) * rng.choice(
        [-1.0, 1.0], (16, 1)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("L,k", [(37, 8), (300, 64), (1000, 256)])
def test_the_selected_sets_are_lax_top_ks_ties_included(case, L, k):
    rng = np.random.default_rng(L + k)
    s = CASES[case](rng, L).astype(np.float32) + 0.0
    nv = rng.integers(0, L + 1, 16).astype(np.int32)
    nv[:4] = (L, k, k - 1, k + 1)
    got = np.asarray(select_topk(jnp.asarray(s), jnp.asarray(nv), k))
    for r in range(16):
        n = int(nv[r])
        want = np.zeros(L, bool)
        if n:
            want[np.asarray(jax.lax.top_k(jnp.asarray(s[r, :n]), min(k, n))[1])] = True
        assert (got[r] == want).all(), (r, n)
        assert got[r].sum() == min(k, n)


def _sets_of_the_engine(model, weights, prompt, mq):
    """The selections layer 0's indexer makes for a prompt fed in chunks of
    ``mq`` through ``sparse_index`` against its paged key pool, [S, S]."""
    cfg = model.config
    lw = model.serving_weights(jnp.float32)["layers"][0]
    S, bs = len(prompt), 8
    x = pangu_moe._rms(weights["embed"][jnp.asarray(prompt)], lw["ln_in"], cfg.rms_norm_eps)
    rope = model.serving_rope(96)
    c_q = pangu_moe._q_latent(cfg, lw, x)
    qi, wi, ki = deepseek_v32._index_proj(cfg, lw, x, c_q, rope[0, :S], rope[1, :S])
    cache = jnp.zeros((16, bs, cfg.index_head_dim), jnp.float32)
    bt = jnp.asarray([[3, 9, 1, 12, 5, 7, 0, 14, 2, 11, 4, 6]], jnp.int32)
    rows = []
    for at in range(0, S, mq):
        n = min(mq, S - at)
        pad = lambda a: jnp.pad(a[at:at + n], ((0, mq - n),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
        dec, now = jnp.asarray([at], jnp.int32), jnp.asarray([n], jnp.int32)
        cu = jnp.asarray([0, n], jnp.int32)
        coords = token_coords(mq, dec, now, cu, 1)
        sel, cache, _ = sparse_index(
            pad(qi), pad(wi), pad(ki), cache, dec, now, cu, bt, coords,
            topk=cfg.index_topk, max_q_len=mq, ctx_block=16)
        if n == 1:       # a row's one token: its set as positions, and no bit of the mask
            assert sel.mask is None or not np.asarray(sel.mask).any()
            row = np.zeros((1, S), bool)
            row[0, np.asarray(sel.idx)[0][np.asarray(sel.ok)[0]]] = True
            rows.append(row)
        else:            # a chunk row: its queries' sets as a mask, and no position
            assert not np.asarray(sel.ok).any()
            rows.append(np.asarray(sel.mask)[:n, :S])
    return np.concatenate(rows), (qi, wi, ki)


@pytest.mark.parametrize("mq", [1, 5, 64])
def test_the_indexer_through_the_pool_selects_what_top_k_selects(built, mq):
    """One-token feeds, chunks that cross ``index_topk`` (5 then 10 tokens:
    the second chunk's queries lie on both sides of 8), and the whole prompt
    in one chunk: every query's set is ``lax.top_k``'s over its own context."""
    model, weights = built
    (prompt,) = _prompts([43], seed=11)
    got, (qi, wi, ki) = _sets_of_the_engine(model, weights, prompt, mq)
    scores = np.asarray(index_scores(qi, wi, ki))               # [S, S], all pairs
    k = TINY["index_topk"]
    for t in range(43):
        want = np.zeros(43, bool)
        want[np.asarray(jax.lax.top_k(jnp.asarray(scores[t, :t + 1]), min(k, t + 1))[1])] = True
        assert (got[t] == want).all(), t
    assert got[20].sum() == k and not got[20, 21:].any()


# -------------------------------------------------- engine against reference
def test_prefill_then_decode_through_both_cache_arrays(built):
    """Prompts shorter and longer than ``index_topk`` and than a launch's
    budget, so the single-step prefill, the mixed scan (chunks of 8 = one
    block, each crossing or past the 8 selected) and the decode scan all
    serve them; every served token's logprob is the reference's."""
    model, weights = built
    prompts = _prompts([5, 23, 40, 9, 17, 61])
    eng, served = _serve(model, prompts)
    assert eng.megasteps > eng.megasteps_mixed >= 1
    assert [len(c) for c in eng.caches] == [3, 3]
    assert eng.caches[0][0].shape[1:] == (8, 24) and eng.caches[1][0].shape[1:] == (8, 16)
    for prompt, (new, lps) in zip(prompts, served):
        table, want = _ref_logprobs(weights, TINY, prompt, new)
        assert np.abs(lps - want).max() < LOGPROB_TOL
        assert (table.argmax(-1) == np.asarray(new)).all()


def test_one_token_rows_gather_their_selection_whatever_the_table_holds():
    """A row's one token has ONE form, the gather, also where a selection is a
    third of what the table can hold (32 of 96).  The served logprobs are the
    reference's, and such a row reads its 32 entries while a chunk row's query
    reads its row's blocked pass (one trip of 96 positions here)."""
    cfg = dict(TINY, index_topk=32)
    model, weights = _build(cfg)
    prompts = _prompts([50, 37, 9])
    eng, served = _serve(model, prompts, new=14)
    fed_back = eng.dsa_queries - (18 + 5)            # the prompts' tokens 32..
    assert fed_back > 0
    assert eng.dsa_positions_selected == 32 * eng.dsa_queries
    assert eng.dsa_positions_read == 96 * (18 + 5) + 32 * fed_back
    for prompt, (new, lps) in zip(prompts, served):
        _, want = _ref_logprobs(weights, cfg, prompt, new)
        assert np.abs(lps - want).max() < LOGPROB_TOL


@pytest.mark.parametrize("quant", ["recent", "dense"])
def test_the_selection_is_not_something_the_tolerance_lets_go(built, quant):
    """A program that attended the most recent positions, or all of them,
    would miss the reference by thousands of tolerances."""
    model, weights = built
    (prompt,) = _prompts([40])
    _, ((new, lps),) = _serve(model, [prompt])
    _, wrong = _ref_logprobs(weights, TINY, prompt, new, quant=quant)
    assert np.abs(lps - wrong).max() > 100 * LOGPROB_TOL


def test_bf16_arithmetic_fails_the_float32_tolerance(built):
    _, weights = built
    cfg = dict(TINY, torch_dtype="bfloat16")
    low = FAMILY.make_weights(cfg, 7)        # the same draws, cast (the bias stays float32)
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
    assert eng.prefix_hit_blocks > 0          # its own blocks, BOTH arrays', published
    lps = np.asarray(eng.pop_token_logprobs()[rid2])
    _, want = _ref_logprobs(weights, TINY, prompt + done, rest)
    assert np.abs(lps - want).max() < LOGPROB_TOL


def test_prefix_cache_and_cow_fork_copy_both_arrays(built):
    """A prefix hit reads another request's ``latent`` AND ``index_k`` blocks;
    a full match forks the last block, and the fork copies both: the tokens
    are the cold engine's, and the copied block holds the source's bytes in
    every array of every layer."""
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
    before = jax.tree_util.tree_map(np.asarray, eng.caches)
    eng._copy_block(1, 2)
    for name, layers, was in zip(("latent", "index_k"), eng.caches, before):
        for now, old in zip(layers, was):
            assert np.array_equal(np.asarray(now)[2], old[1]), name
            assert np.asarray(now)[2].any(), name


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


def test_a_selection_of_everything_is_the_dense_pass():
    """``latent_attention(selection=)`` with every position selected (a
    one-token row's as positions, a chunk row's as a mask) is the call without
    it, and a selection without one position changes the rows that could see
    it and no other."""
    rng = np.random.default_rng(3)
    T, H, W, C, bs = 12, 2, 24, 16, 8
    q = jnp.asarray(rng.normal(size=(T, H, W)), jnp.float32)
    ent = jnp.asarray(rng.normal(size=(T, W)), jnp.float32)
    cache = jnp.asarray(rng.normal(size=(8, bs, W)), jnp.float32)
    dec, now = jnp.asarray([13, 4], jnp.int32), jnp.asarray([1, 11], jnp.int32)
    cu = jnp.asarray([0, 1, 12], jnp.int32)
    bt = jnp.asarray([[1, 5, -1], [2, 0, 7]], jnp.int32)
    kw = dict(rank=C, max_q_len=11, scale=0.3, ctx_block=8)
    plain, _ = latent_attention(q, ent, cache, dec, now, cu, bt, **kw)
    idx = jnp.tile(jnp.arange(16, dtype=jnp.int32), (2, 1))
    ok = idx < jnp.asarray([[14], [0]])               # row 0's 14 positions; row 1 feeds a chunk
    everything = Selection(idx, ok, jnp.ones((T + 11, 24), bool))
    same, _ = latent_attention(q, ent, cache, dec, now, cu, bt, selection=everything, **kw)
    assert np.abs(np.asarray(same) - np.asarray(plain)).max() < 1e-6
    hidden = Selection(idx, ok & (idx != 2), everything.mask.at[:, 2].set(False))
    got, _ = latent_attention(q, ent, cache, dec, now, cu, bt, selection=hidden, **kw)
    assert np.abs(np.asarray(got) - np.asarray(plain)).max(axis=(1, 2)).min() > 1e-4


def test_what_the_passes_bring_is_counted_by_their_own_arithmetic():
    """``selection_reads``: a one-token row past ``topk`` brings the K gathered
    entries, a chunk row's queries at positions >= ``topk`` each the whole
    blocks of their row's trips; rows at rest and short contexts bring none."""
    dec = jnp.asarray([13, 4, 40, 3, 70, 0], jnp.int32)
    now = jnp.asarray([1, 11, 8, 1, 0, 30], jnp.int32)
    got = selection_reads(dec, now, topk=8, gathered=8, block_size=8, blocks_per_seq=12,
                          ctx_block=16)
    # row 0: 8; row 1: positions 8..14 are 7 queries x ceil(15 / 16) x 16;
    # row 2: 8 queries x 3 trips x 16; row 3: a context of 4; row 4 at rest;
    # row 5: 22 queries x 2 trips x 16
    assert int(got) == 8 + 7 * 16 + 8 * 48 + 0 + 0 + 22 * 32


# ------------------------------------------------------------- routing
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_grouped_routing_is_the_references(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(70, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 16)) / 8, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.3, jnp.float32)
    idx, wt = deepseek_v32.route_grouped(x, w, bias, 4, 2.5, 4, 2)
    ridx, rwt = REFERENCE.route(x, w, bias, 4, 2.5, 4, 2)
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(ridx)))
    order, rorder = np.argsort(np.asarray(idx)), np.argsort(np.asarray(ridx))
    assert np.abs(np.take_along_axis(np.asarray(wt), order, 1)
                  - np.take_along_axis(np.asarray(rwt), rorder, 1)).max() < 1e-6
    # a token's four experts lie in two groups of four, the weights sum to the
    # scale, and they are the sigmoid's (the bias moves the choice alone)
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(idx))
    assert np.abs(np.asarray(wt).sum(-1) - 2.5).max() < 1e-5
    g = np.asarray(jax.nn.sigmoid(x @ w))
    picked = np.take_along_axis(g, np.asarray(idx), 1)
    assert np.abs(np.asarray(wt) - 2.5 * picked / picked.sum(-1, keepdims=True)).max() < 1e-5
    plain, _ = deepseek_v32.route_grouped(x, w, jnp.zeros(16), 4, 2.5, 4, 2)
    assert not np.array_equal(np.sort(np.asarray(plain)), np.sort(np.asarray(idx)))


def test_the_share_adds_up():
    """The routed parts that the four shares of a 16-expert layer give, with
    the shared expert counted once, equal the uncut reference's expert layer
    under the grouped routing: in the program and in the reference alike."""
    uncut = dict(TINY, n_routed_experts=16, experts_held=[0, 16])
    weights = FAMILY.make_weights(uncut, 13)
    p = weights["layers"][1]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(50, 64)), jnp.float32)
    idx, w = REFERENCE.route(x, p["router"], p["router_bias"], 4, 2.5, 4, 2)
    shared = REFERENCE.swiglu(x, p["sg"], p["su"], p["sd"])
    whole = shared + sum(
        REFERENCE.weight_of(idx, w, e)[:, None] * REFERENCE.swiglu(
            x, p["eg"][e], p["eu"][e], p["ed"][e]) for e in range(16))
    cfg = FAMILY.model_config(uncut)
    parts, picks = [], 0
    for lo in (0, 4, 8, 12):
        pidx, pw = deepseek_v32._router_of(cfg, p)(x, p["router"], 4, 2.5)
        y, n = held_experts(x, pidx, pw, p["eg"][lo:lo + 4], p["eu"][lo:lo + 4],
                                      p["ed"][lo:lo + 4], lo, tile=8)
        parts.append(np.asarray(y))
        picks += int(n)
    assert picks == 50 * 4                      # every pick falls on exactly one share
    assert np.abs(np.asarray(shared) + sum(parts) - np.asarray(whole)).max() < 2e-5
    assert np.abs(parts[0]).max() > 0.01        # and a share is not nothing
    # the whole layer through the model's own function, a share at a time
    total = 0.0
    for lo in (0, 4, 8, 12):
        share = FAMILY.model_config(dict(uncut, n_routed_experts=4, experts_held=[lo, lo + 4]))
        leaves = dict(p, eg=p["eg"][lo:lo + 4], eu=p["eu"][lo:lo + 4], ed=p["ed"][lo:lo + 4])
        y, _ = pangu_moe._moe_ffn(share, leaves, x, router=deepseek_v32._router_of(share, p))
        total = total + np.asarray(y) - np.asarray(shared)
    assert np.abs(total + np.asarray(shared) - np.asarray(whole)).max() < 2e-5


# ------------------------------------------------------------ the refusals
def test_int8_cache_and_block_transfer_refuse_with_the_typed_error(built):
    model, _ = built
    with pytest.raises(ValueError, match="index_k"):
        ServingEngine(model, cache_quant="int8", **ENGINE)
    eng = ServingEngine(model, **ENGINE)
    spec = eng.cache_spec
    assert [n for n, _ in spec.arrays] == ["latent", "index_k"]
    assert "latent" in spec.why_not and "index_k" in spec.why_not
    for call in (lambda: eng.export_blocks(["h"]), lambda: eng.export_blocks_packed(["h"]),
                 lambda: eng.import_blocks({}), lambda: eng.import_blocks_packed({}, b"")):
        with pytest.raises(ValueError, match="index_k"):
            call()


def test_load_weights_refuses_another_geometry(built):
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    other, _ = _build(dict(TINY, index_topk=4))
    with pytest.raises(ValueError, match="geometry"):
        eng.load_weights(other)
    again, _ = _build(seed=8)
    assert eng.load_weights(again, version="v1") == "v1"


# ------------------------------------------------- names, spans and counters
# (regular expressions: the scopes inside the loops over the context lie under
# ``while/body/``, once for each loop around them)
LOOP = "(?:while/body/)+"
SCOPES = ("embed", "norm", "latent_proj", "indexer", "indexer/index_proj",
          "indexer/index_write", "indexer/" + LOOP + "index_gather",
          "indexer/" + LOOP + "index_scores", "indexer/index_topk", "latent_attention",
          "latent_attention/kv_write", "latent_attention/" + LOOP + "kv_gather",
          "latent_attention/" + LOOP + "scores", "latent_attention/" + LOOP + "values",
          "attn_out", "router", "experts", "experts/while/body", "shared_expert", "mlp",
          "head", "sample")


@pytest.fixture(scope="module")
def dsa_texts(built):
    return programs.lowered(ServingEngine(built[0], spec_k=2, **ENGINE), debug_info=True)


@pytest.mark.parametrize("kind", ["step", "mega", "mixed", "spec"])
def test_lowered_program_names_the_scopes(dsa_texts, kind):
    import re

    text = dsa_texts[kind]
    want = SCOPES + ("latent_attention/select_gather",) + (() if kind == "step"
                                                           else ("scan_carry",))
    if kind == "mega":       # rows of one token only: their selection is gathered, no loop
        want = tuple(s.replace(LOOP, "") if s.startswith("latent_attention") else s
                     for s in want if s != "latent_attention/" + LOOP + "kv_gather")
    missing = [s for s in want if not re.search(rf'["/(]{s}[/)"]', text)]
    assert not missing, f"{kind}: no operation under {missing}"
    assert f"jit_{'spec_verify' if kind == 'spec' else kind}" in text
    assert "post_norm" not in text                   # two norms a layer, no sandwich


def test_the_selections_counters_are_monotone_and_ride_the_harvest_span(built):
    model, _ = built
    eng = ServingEngine(model, **ENGINE)
    harvests = programs.harvests(eng)
    names = ("dsa_queries", "dsa_positions_scored", "dsa_positions_selected",
             "dsa_positions_read", "moe_tokens", "moe_local_picks")
    assert all(getattr(eng, n) == 0 for n in names)
    for p in _prompts([20, 9]):
        eng.add_request(p, max_new_tokens=6)
    last = (0,) * len(names)
    while eng._queue or eng._active:
        eng.step()
        now = tuple(getattr(eng, n) for n in names)
        assert all(a >= b for a, b in zip(now, last))
        last = now
    # ONE layer's count: the queries whose context exceeds 8 positions are the
    # prompts' tokens 8.. (12 and 1) and the 5 + 5 tokens fed back
    assert eng.dsa_queries == 12 + 1 + 10
    assert eng.dsa_positions_selected == 8 * eng.dsa_queries
    scored = sum(range(9, 21)) + sum(range(21, 26)) + 9 + sum(range(10, 15))
    assert eng.dsa_positions_scored == scored
    # the tokens fed back are rows of one token, whose selection is GATHERED:
    # 8 entries each; a prompt's query reads its row's blocked pass, one trip
    # of 96 positions (``latent_attention.selection_reads``)
    assert eng.dsa_positions_read == 96 * (12 + 1) + 8 * 10
    assert eng.attn_positions_live == 20 + 9 + sum(range(21, 26)) + sum(range(10, 15))
    assert eng.moe_tokens == 2 * (29 + 10)
    assert eng.state_summary()["sparse_attention"] == {
        "queries": eng.dsa_queries, "positions_scored": scored,
        "positions_selected": 8 * eng.dsa_queries, "positions_read": eng.dsa_positions_read}
    seen = [h[-1] for h in harvests]
    assert seen and all(set(names) <= set(a) for a in seen)
    for n in names:
        assert sum(a[n] for a in seen) == getattr(eng, n), n


def test_a_model_without_an_indexer_counts_no_selection():
    P.seed(0)
    eng = ServingEngine(LlamaForCausalLM(llama_tiny()).eval(), **ENGINE)
    eng.add_request([3, 17, 101], max_new_tokens=6)
    eng.run()
    assert eng.state_summary()["sparse_attention"] == {
        "queries": 0, "positions_scored": 0, "positions_selected": 0, "positions_read": 0}
