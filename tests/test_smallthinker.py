"""SmallThinker (attention layers of two kinds by a published layout: global
without position encoding, sliding-window with RoPE; ReGLU experts under a
router that reads the attention's input) at a tiny size on the CPU, seeded
random weights: the model's own ``forward``; the serving engine's trunk over
TWO pools, a block table a KIND of cache layer, with the window kind's blocks
given back while a row runs (the step, the decode scan, the mixed scan; contexts
that cross the window several times; one slot and four tenants; blocks that one
row gave back and another took; ``evict`` and a recompute from 0; the queue's
head waiting on the WINDOW pool; a shared block that outlives one owner); the
typed refusals; the names in the compiled programs; the counters; all held to
the plain float32 reference (benchmark/references/swa_gqa_moe.py), which shares
nothing with the program.  Window 24 positions = 3 blocks of 8."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine, ServingFrontend
from paddle_tpu.inference.serving import BlockManager, control_layout
from paddle_tpu.inference.serving_model import CacheKind, CacheSpec
from paddle_tpu.models import SmallThinkerConfig, pangu_moe, smallthinker, smallthinker_tiny
from paddle_tpu.ops import held_experts as he

from benchmark.harness import loader

import programs
from programs import ENGINE

FAMILY = loader.load_module("families", "swa_gqa_moe")
REFERENCE = loader.load_module("references", "swa_gqa_moe")
TINY = programs.TINY["smallthinker"]
W, BS = TINY["sliding_window_size"], ENGINE["block_size"]
CONTROLS = ("window_off", "rope_all", "router_post")
POOLS = {"global": 48, "window": 28}

# A float32 engine and the float32 reference differ by the order of their sums
# alone (a blocked online softmax against a whole one, experts added tile by
# tile against expert by expert): 1e-6 to 5e-6 nats on a served token's
# log-probability.  1e-4 is twenty times that; each control OF THE MECHANISM
# (the window forgotten, RoPE on the global layers, the router reading the
# expert layer's own input) moves it by 0.05-2 nats on a context past the
# window, five hundred times the tolerance, and so does bf16 arithmetic.
LOGPROB_TOL = 1e-4


@pytest.fixture(autouse=True)
def _no_fleet_group():
    set_hybrid_communicate_group(None)


@pytest.fixture(scope="module")
def built():
    return programs.build("smallthinker")


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
def test_config_keeps_the_published_names_and_refuses_another_model():
    cfg = SmallThinkerConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.moe_num_primary_experts,
            cfg.moe_num_active_primary_experts, cfg.moe_ffn_hidden_size,
            cfg.sliding_window_size, cfg.vocab_size) == (
                52, 2560, 28, 4, 128, 64, 6, 768, 4096, 151936)
    assert cfg.sliding_window_layout == cfg.rope_layout == [0, 1, 1, 1] * 13
    assert len(cfg.layers_of(False)) == 13 and len(cfg.layers_of(True)) == 39
    assert cfg.num_attention_heads * cfg.head_dim != cfg.hidden_size     # 3584, not 2560
    built = FAMILY.model_config(TINY)
    assert built.sliding_window_layout == [0, 1, 1, 1] and built.experts_held == (0, 8)
    for bad in (dict(moe_primary_router_apply_softmax=False), dict(rope_scaling={"type": "yarn"}),
                dict(tie_word_embeddings=True), dict(sliding_window_layout=[1, 1, 1, 1]),
                dict(rope_layout=[0, 2, 1, 1]), dict(experts_held=(4, 9))):
        with pytest.raises(ValueError):
            smallthinker_tiny(**bad)


def test_relu_between_gate_and_up_and_the_softmax_of_the_chosen():
    """``held_experts(activation="relu")`` is a ReGLU a pick, ``silu`` stays the
    default; ``route_chosen`` takes the largest logits and a softmax over THOSE,
    which is the softmax over all renormalised over the chosen."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(11, 16)), jnp.float32)
    eg, eu = (jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32) for _ in range(2))
    ed = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(11, 4)), jnp.float32)
    idx, w = pangu_moe.route_chosen(logits, 2)
    full = jax.nn.softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(full, idx, axis=-1)
    np.testing.assert_allclose(w, chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.argsort(-np.asarray(logits))[:, :2], -1)).all()
    for name, act in (("relu", jax.nn.relu), ("silu", jax.nn.silu)):
        y, picks = he.held_experts(x, idx, w, eg, eu, ed, 0, tile=4, activation=name)
        want = sum(np.asarray(w)[:, j, None] * np.stack([
            np.asarray((act(x[t] @ eg[e]) * (x[t] @ eu[e])) @ ed[e])
            for t, e in enumerate(np.asarray(idx)[:, j])]) for j in range(2))
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
        assert int(picks) == 22
    default, _ = he.held_experts(x, idx, w, eg, eu, ed, 0, tile=4)
    np.testing.assert_array_equal(np.asarray(default), np.asarray(y))       # silu, as it was


def test_forward_agrees_with_the_reference_and_the_controls_do_not(built):
    """Whole sequences under an explicit mask, 70 tokens over a window of 24."""
    model, weights = built
    ids = np.asarray(_prompts([70], seed=2)[0], np.int32)
    got = np.asarray(model(jnp.asarray(ids[None]))._value[0], np.float64)
    rows = np.arange(len(ids))
    want = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows), np.float64)
    assert np.abs(got - want).max() < 5e-5
    for control in CONTROLS:
        off = np.asarray(REFERENCE.logits_at(weights, TINY, ids, rows, quant=control))
        assert np.abs(off - want).max() > 0.05, control
    # the window is the difference only PAST it
    short = np.asarray(REFERENCE.logits_at(weights, TINY, ids[:W], rows[:W], quant="window_off"))
    assert np.abs(short - want[:W]).max() < 1e-5


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
    reference's, which ``window_off``, ``rope_all`` and ``router_post`` each
    fail; the window kind's blocks were given back on the way and taken again
    by other rows."""
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


def test_one_slot_four_tenants_and_blocks_that_changed_hands(built):
    """ONE slot, four tenants of unequal length one after another, then two
    slots over a window pool so small that every block is taken again and again:
    a block the window gave back and another row took is never read by the
    first (its table entry reads as no block), so every token stays the
    reference's."""
    model, weights = built
    prompts = _prompts([66, 12, 81, 33], seed=5)
    eng, served = _serve(model, prompts, new=10, max_batch_size=1)
    _held_to_reference(weights, prompts, served)
    eng = ServingEngine(model, **{**ENGINE, "max_batch_size": 2,
                                  "num_blocks": {"global": 30, "window": 18}})
    given, taken = [], []
    free, allocate = eng.pools[1].free, eng.pools[1].allocate
    eng.pools[1].free = lambda blocks: (given.extend(blocks), free(blocks))[1]
    eng.pools[1].allocate = lambda n: (lambda got: (taken.append(list(got)), got)[1])(allocate(n))
    rids = [eng.add_request(p, max_new_tokens=14, sampling={"logprobs": True}) for p in prompts]
    held = {}                 # block -> the one row that holds it, at every step
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
        held = now
    out, lps = dict(eng._finished), eng.pop_token_logprobs()
    _held_to_reference(weights, prompts, [(out[r], np.asarray(lps[r])) for r in rids])
    first_owner = set(taken[0])
    assert first_owner & set(given) and any(first_owner & set(t) for t in taken[1:])
    assert sum(len(t) for t in taken) > 18 and eng.window_blocks_released > 0   # reuse
    with pytest.raises(RuntimeError, match="double-free"):
        eng.pools[1].free([given[0]])               # a block given back is not a row's


def test_evict_and_a_recompute_from_zero_walk_the_window_again(built):
    model, weights = built
    prompt = _prompts([58], seed=4)[0]
    _, [(whole, _)] = _serve(model, [prompt], new=24)
    eng = ServingEngine(model, **{**ENGINE, "num_blocks": POOLS})
    other = eng.add_request(_prompts([11], seed=9)[0], max_new_tokens=40)
    rid = eng.add_request(prompt, max_new_tokens=24)
    while rid not in eng._active or len(eng._active[rid].generated) < 9:
        eng.step()
    held = eng.state_summary()["pools"][1]
    assert 0 < held["blocks_held"] <= held["blocks_reserved"]
    req = eng.evict(rid)
    assert req.kind_blocks == [] and req.prefill_pos == 0
    assert eng.state_summary()["pools"][1]["blocks_reserved"] == eng._kind_hold(1, 7)
    again = eng.add_request(req.prompt + req.generated, max_new_tokens=24 - len(req.generated),
                            sampling={"logprobs": True})
    released = eng.window_blocks_released
    out = eng.run()
    assert req.generated + out[again] == whole and other in out
    assert eng.window_blocks_released > released      # the recompute freed as it went
    _, want = _ref_logprobs(weights, req.prompt + req.generated, out[again])
    assert np.abs(want - np.asarray(eng.pop_token_logprobs()[again])).max() < LOGPROB_TOL


def test_the_queues_head_waits_on_the_window_pool_and_an_eviction_admits_it(built):
    """The window pool reserves a row's WORST hold at admission: with room in
    the global pool and a free slot, a request still waits while the window
    pool's reservations are full (``admission_waits`` says on which pool); the
    control plane's preemption (``evict``) of the running row admits it, and the
    evicted row recomputed from 0 gives the tokens it would have given."""
    model, weights = built
    eng = ServingEngine(model, **{**ENGINE, "num_blocks": {"global": 40, "window": 12}})
    hold = eng._kind_hold(1, 12)
    assert hold == -(-(W + eng._reach) // BS) + 1 == 8 and eng._kind_hold(1, 3) == 3
    a, b = _prompts([60, 50], seed=6)
    _, [(whole_a, _), (whole_b, _)] = _serve(model, [a, b], new=16)
    ra = eng.add_request(a, max_new_tokens=16)
    rb = eng.add_request(b, max_new_tokens=16)
    for _ in range(3):
        eng.step()
    st = eng.state_summary()
    assert list(st["active"]) == [ra] and st["free_slots"] == 3 and st["queue_depth"] == 1
    window = st["pools"][1]
    assert window["blocks_reserved"] == hold and window["admission_waits"] > 0
    assert st["pools"][0]["admission_waits"] == 0 and st["blocks_free"] == 40 - 10 + 12 - hold
    req = eng.evict(ra)                                    # what a preemption does
    assert eng.state_summary()["pools"][1]["blocks_reserved"] == 0
    back = eng.add_request(req.prompt + req.generated, max_new_tokens=16 - len(req.generated))
    out = eng.run()
    assert out[rb] == whole_b and req.generated + out[back] == whole_a


def test_a_shared_window_block_outlives_one_owner():
    """``BlockManager`` a kind: what the window gives back goes through ``free``,
    refcounted as ever, so a block two rows share stays live until the second
    lets go; a third release is loud."""
    pool = BlockManager(4)
    (b,) = pool.allocate(1)
    pool.fork(b)
    pool.free([b])                                          # the first owner's window moved on
    assert pool.ref_count(b) == 1 and pool.num_free == 3
    pool.free([b])
    assert pool.ref_count(b) == 0 and pool.num_free == 4
    with pytest.raises(RuntimeError, match="double-free"):
        pool.free([b])


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
def test_a_spec_says_its_kinds_and_one_kind_packs_as_it_always_did(built):
    model, _ = built
    spec = model.serving_cache_spec()
    assert spec.kinds == (CacheKind("global", 1), CacheKind("window", 3, W))
    assert spec.layers == 4 and not spec.blocks_are_positions and W in spec.key
    eng = ServingEngine(model, **{**ENGINE, "num_blocks": POOLS})
    assert [m.num_blocks for m in eng.pools] == [48, 28] and eng.blocks is eng.pools[0]
    assert [a.shape[0] for a in eng.caches[0]] == [48, 28, 28, 28]
    assert len(eng.kind_tables) == 2 and eng.block_tables is eng.kind_tables[0]
    B, P = eng.B, eng.P
    for kind, n in (("step", 32), ("mega", 0), ("mixed", 32), ("spec", 2)):
        one, two = control_layout(kind, B, P, n), control_layout(kind, B, P, n, 2)
        assert one == control_layout(kind, B, P, n, 1)
        assert one.rows[-1] == ("bt", (B, P), "i") and two.rows[:-1] == one.rows
        assert two.rows[-1] == ("bt.1", (B, P), "i") and two.size == one.size + B * P
    with pytest.raises(ValueError, match="num_blocks names"):
        ServingEngine(model, **{**ENGINE, "num_blocks": {"windows": 4}})
    same = ServingEngine(model, **{**ENGINE, "num_blocks": 20})
    assert [m.num_blocks for m in same.pools] == [20, 20]
    for bad in (dict(kinds=(CacheKind("a", 1, 8), CacheKind("b", 3))),       # the first windowed
                dict(kinds=(CacheKind("a", 1), CacheKind("b", 2))),          # layers do not add up
                dict(kinds=(CacheKind("a", 1), CacheKind("a", 3, 8)))):      # one name twice
        with pytest.raises(ValueError, match="kinds"):
            CacheSpec(arrays=spec.arrays, layers=4, key=(), **bad)
    # all layers global: one kind, one table, nothing refused
    plain = FAMILY.build_model(dict(TINY, sliding_window_layout=[0] * 4, rope_layout=[0, 1, 1, 1]))
    assert plain.serving_cache_spec().kinds == (CacheKind("global", 4),)
    assert plain.serving_cache_spec().blocks_are_positions


def test_what_takes_blocks_to_be_all_positions_refuses_with_the_typed_error(built):
    model, _ = built
    why = "GIVE BACK the blocks behind"
    assert why in model.serving_cache_spec().why_not
    with pytest.raises(ValueError, match="prefix_cache cannot be used.*" + why):
        ServingEngine(model, prefix_cache=True, **ENGINE)
    with pytest.raises(ValueError, match="spec_k > 0 cannot be used.*" + why):
        ServingEngine(model, spec_k=2, **ENGINE)
    with pytest.raises(ValueError, match="cache_quant='int8' cannot be used.*" + why):
        ServingEngine(model, cache_quant="int8", **ENGINE)
    eng = ServingEngine(model, **ENGINE)                  # "auto" serves, the cache off
    assert eng.prefix_cache_enabled is False
    for call in (lambda: eng.export_blocks(["h"]), lambda: eng.export_blocks_packed(["h"]),
                 lambda: eng.import_blocks({}), lambda: eng.import_blocks_packed({}, b""),
                 lambda: eng._copy_block(0, 1)):
        with pytest.raises(ValueError, match=why):
            call()


# ------------------------------------------------------- names and counters
SCOPES = ("embed", "norm", "router", "attn_proj", "paged_attention", "rope", "kv_write",
          "attn_out", "experts", "head")


@pytest.mark.parametrize("kind", ["step", "mega", "mixed"])
def test_lowered_program_names_the_scopes_and_routes_before_attention(built, kind):
    eng = ServingEngine(built[0], **{**ENGINE, "num_blocks": POOLS})
    text = programs.lowered(eng, debug_info=True, kinds=(kind,))[kind]
    want = SCOPES + (() if kind == "step" else ("scan_carry",))
    missing = [s for s in want if not re.search(rf'["/(]{s}[/)"]', text)]
    assert not missing, f"{kind}: no operation under {missing}"
    assert f"jit_{kind}" in text and "shared_expert" not in text
    # a layer's router comes BEFORE its attention: the first of each in the text
    assert re.search(r'["/(]router[/)"]', text).start() < re.search(
        r'["/(]paged_attention[/)"]', text).start()


def test_the_counters_are_monotone_and_ride_the_harvest_span(built):
    model, _ = built
    eng = ServingEngine(model, **{**ENGINE, "num_blocks": POOLS})
    harvests = programs.harvests(eng)
    names = ("moe_tokens", "moe_local_picks", "experts_touched", "expert_tiles",
             "expert_tile_rows",
             "expert_tile_rows_live", "expert_rows_grouped", "attn_positions_live",
             "attn_positions_read", "kv_write_tokens")
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
    assert eng.moe_local_picks == eng.expert_tile_rows_live == 2 * eng.moe_tokens
    kc = eng.kind_counts
    # ONE layer of a kind's: both kinds had the same rows in context; the window
    # layer spared what lay behind each fed row's first key
    assert kc["attn_positions_live.global"] == kc["attn_positions_live.window"] \
        == eng.attn_positions_live
    assert kc["attn_positions_read.global"] == eng.attn_positions_read
    # the 60-token prompt went in steps of 32 and 28 (the second's first key is
    # 32 - 23), then one token a time at 60 .. 64 cached, the last one twice (frozen)
    assert kc["window_positions_spared"] == (32 - 23) + sum(
        d - (W - 1) for d in (60, 61, 62, 63, 64, 64))
    assert eng.window_blocks_released == (64 - W + 1) // BS == 5
    st = eng.state_summary()
    assert st["attention_by_kind"] == kc and st["window_blocks_released"] == 5
    assert [p["kind"] for p in st["pools"]] == ["global", "window"]
    assert st["blocks_total"] == 48 + 28 == st["blocks_free"]
    seen = [h[-1] for h in harvests]
    assert seen and all(set(names + by_kind) <= set(a) for a in seen)
    for n in names:
        assert sum(a[n] for a in seen) == getattr(eng, n), n
    for n in by_kind:
        assert sum(a[n] for a in seen) == kc[n], n
    # the engine's totals so far ride the span too, monotone
    rel = [a["window_blocks_released"] for a in seen]
    assert rel == sorted(rel) and rel[0] == 0 and all("window_blocks_held" in a for a in seen)


def test_a_model_of_one_kind_counts_none_and_has_one_pool():
    import paddle_tpu as P
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    P.seed(0)
    eng = ServingEngine(LlamaForCausalLM(llama_tiny()).eval(), **ENGINE)
    harvests = programs.harvests(eng)
    eng.add_request(_prompts([20])[0], max_new_tokens=4)
    eng.run()
    st = eng.state_summary()
    assert len(st["pools"]) == 1 and st["pools"][0]["kind"] == "all"
    assert st["pools"][0]["window"] is None and st["attention_by_kind"] == {}
    assert st["window_blocks_released"] == 0 and st["blocks_total"] == eng.blocks.num_blocks
    assert all("window_blocks_released" not in h[-1] for h in harvests)
