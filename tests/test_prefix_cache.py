"""Automatic prefix caching (ISSUE 5 tentpole): refcounted copy-on-write
KV blocks, cached-prefix prefill skip, prefix-affinity routing.

Acceptance-critical properties checked here:
* BlockManager refcount lifecycle: share -> free -> LRU-park -> revive /
  evict -> reuse, with the double-free guards still firing under sharing;
* copy-on-write isolation: a writer admitted onto shared blocks never
  mutates the cached original (bit-checked on the device cache);
* engine parity: greedy outputs are token-identical cache-on vs
  cache-off, while prefill tokens actually computed drop by the shared
  full-block fraction — including the evict -> resume path, whose
  recompute hits the cache the eviction itself published;
* cache_quant='int8' + prefix cache is a hard, explained error;
* the frontend routes a prompt to the replica with the most cached
  prefix and folds hit/miss/eviction counters into ServingMetrics,
  which ``merge`` recomputes fleet-wide.
"""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference import (
    BlockManager,
    ServingEngine,
    ServingFrontend,
    ServingMetrics,
)
from paddle_tpu.inference.serving import prefix_block_hash, prompt_block_hashes

pytestmark = pytest.mark.quick

ENGINE = dict(max_batch_size=2, max_seq_len=64, block_size=8,
              token_budget=16)
SHARED = list(range(30, 46))        # 16 tokens = exactly 2 full blocks


@pytest.fixture(scope="module")
def model():
    # single-process sub-tiny model (see test_serving_control_plane.py:
    # 1 layer / 64 hidden keeps the many engine compiles affordable)
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    set_hybrid_communicate_group(None)
    P.seed(11)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        num_hidden_layers=1, num_attention_heads=2,
        max_position_embeddings=256))


def ref_greedy(model, prompt, n):
    from paddle_tpu.models.generation import generate

    ids = P.to_tensor(np.asarray(prompt, np.int32)[None, :])
    # the fixed-shape path (two programs): with growing caches every op of the
    # forward compiles again at every length, most of this reference's seconds
    out = generate(model, ids, max_new_tokens=n, do_sample=False,
                   use_static_cache=True)
    return list(np.asarray(out.numpy()).reshape(-1))


class TestBlockManagerRefcounts:
    def test_share_free_park_revive_evict_reuse(self):
        bm = BlockManager(4)
        (b0,) = bm.allocate(1)
        assert bm.publish(b0, "h0")
        bm.fork(b0)                         # second sequence shares it
        assert bm.ref_count(b0) == 2
        bm.free([b0])
        assert bm.ref_count(b0) == 1        # still live for the other owner
        assert bm.lookup("h0") == b0
        bm.free([b0])                       # last owner: parked, not freed
        assert bm.ref_count(b0) == 0
        assert bm.lookup("h0") == b0        # content still addressable
        assert bm.num_evictable == 1
        assert bm.num_free == 4             # cached blocks count as capacity
        bm.fork(b0)                         # revival from the LRU
        assert bm.ref_count(b0) == 1 and bm.num_evictable == 0
        bm.free([b0])
        # eviction happens only when the true free list runs dry
        out = bm.allocate(4)
        assert sorted(out) == [0, 1, 2, 3]
        assert bm.evictions == 1
        assert bm.lookup("h0") is None      # hash dropped with the eviction

    def test_lru_evicts_oldest_cached_first(self):
        bm = BlockManager(3)
        a, b, c = bm.allocate(3)
        bm.publish(a, "ha")
        bm.publish(b, "hb")
        bm.free([a])
        bm.free([b])
        bm.free([c])                        # unpublished -> true free list
        (x,) = bm.allocate(1)
        assert x == c and bm.evictions == 0  # free list before eviction
        (y,) = bm.allocate(1)
        assert y == a and bm.evictions == 1  # oldest cached block goes first
        assert bm.lookup("ha") is None and bm.lookup("hb") == b

    def test_double_free_guards_fire_under_sharing(self):
        bm = BlockManager(4)
        (b,) = bm.allocate(1)
        bm.publish(b, "h")
        bm.fork(b)
        bm.free([b])
        bm.free([b])                        # refcount 0: parked in LRU
        with pytest.raises(RuntimeError, match="double-free"):
            bm.free([b])                    # a cached block is NOT freeable
        (a,) = bm.allocate(1)
        with pytest.raises(RuntimeError, match="repeated"):
            bm.free([a, a])                 # per-call lists must be unique
        bm.free([a])
        with pytest.raises(RuntimeError, match="free list"):
            bm.fork(a)                      # only live/cached blocks share
        with pytest.raises(RuntimeError, match="not live"):
            bm.publish(a, "h2")

    def test_can_allocate_sees_cached_blocks_as_capacity(self):
        bm = BlockManager(2)
        blocks = bm.allocate(2)
        for i, blk in enumerate(blocks):
            bm.publish(blk, f"h{i}")
        bm.free(blocks)
        assert bm.can_allocate(2)           # a warm cache is not a full pool
        out = bm.allocate(2)
        assert sorted(out) == sorted(blocks) and bm.evictions == 2

    def test_chain_hash_commits_to_whole_prefix(self):
        # same block content under different parents must not collide —
        # that is what makes hash equality imply KV equality
        h1 = prefix_block_hash(None, [1, 2, 3, 4])
        h2 = prefix_block_hash(h1, [1, 2, 3, 4])
        assert h1 != h2
        assert prompt_block_hashes([1, 2, 3, 4, 1, 2, 3, 4], 4) == [h1, h2]
        assert prompt_block_hashes([1, 2, 3], 4) == []  # partial tail: none


class TestEnginePrefixCache:
    def test_parity_and_prefill_skip_shared_prefix(self, model):
        """≥4 requests sharing a 2-block prefix: greedy outputs identical
        to a cache-off engine (and to generate()), while prefill tokens
        computed drop by exactly the shared full blocks."""
        tails = [[7, 9, 11], [5, 2], [8, 8, 8, 8], [250, 3]]
        prompts = [SHARED + t for t in tails]

        def serve(prefix_cache):
            eng = ServingEngine(model, prefix_cache=prefix_cache, **ENGINE)
            outs = []
            # first request alone (publishes the prefix on retirement),
            # then the rest together
            r0 = eng.add_request(prompts[0], max_new_tokens=6)
            outs.append(eng.run()[r0])
            rids = [eng.add_request(p, max_new_tokens=6) for p in prompts[1:]]
            rest = eng.run()
            outs.extend(rest[r] for r in rids)
            return eng, outs

        off, outs_off = serve(False)
        on, outs_on = serve("auto")
        assert outs_on == outs_off
        for p, o in zip(prompts, outs_on):
            assert o == ref_greedy(model, p, 6)
        # requests 1..3 each skipped the 16 shared-prefix tokens
        assert off.prefix_hit_blocks == 0
        assert on.prefix_hit_blocks == 2 * 3
        assert (off.prefill_tokens_computed - on.prefill_tokens_computed
                == len(SHARED) * 3)

    def test_fully_cached_prompt_cow_isolation(self, model):
        """A prompt that is 100% cached full blocks re-feeds exactly one
        token into a copy-on-write fork; the shared original block is
        bit-identical before and after the writer's whole run."""
        eng = ServingEngine(model, **ENGINE)
        r0 = eng.add_request(SHARED, max_new_tokens=6)
        out0 = eng.run()[r0]
        h0, h1 = prompt_block_hashes(SHARED, eng.bs)
        b0, b1 = eng.blocks.lookup(h0), eng.blocks.lookup(h1)
        assert b0 is not None and b1 is not None
        k_before = np.asarray(eng.key_caches[0][b1])
        v_before = np.asarray(eng.value_caches[0][b1])

        r1 = eng.add_request(SHARED, max_new_tokens=6)
        eng.step()
        req = eng._active[r1]
        # full match: only the final prompt token re-prefills...
        assert req.cached_prefix_tokens == len(SHARED) - 1
        # ...into a private copy — block 0 shared, block 1 forked
        assert req.blocks[0] == b0 and req.blocks[1] != b1
        out1 = [t for t in eng.run()[r1]]
        assert out1 == out0 == ref_greedy(model, SHARED, 6)
        np.testing.assert_array_equal(k_before,
                                      np.asarray(eng.key_caches[0][b1]))
        np.testing.assert_array_equal(v_before,
                                      np.asarray(eng.value_caches[0][b1]))

    def test_evict_resume_hits_cache_token_identical(self, model):
        """Recompute preemption is nearly free: the eviction publishes the
        victim's blocks, so the resume's prefill (prompt + generated)
        finds its own prefix cached — and the final token stream is
        identical to an unpreempted run."""
        prompt = SHARED + [7, 9, 11]
        full = ref_greedy(model, prompt, 8)
        eng = ServingEngine(model, **ENGINE)
        r1 = eng.add_request(prompt, max_new_tokens=8)
        for _ in range(2):   # the 19-token prompt prefills in two steps
            eng.step()       # (one more would megastep to completion)
        req = eng.evict(r1)
        assert req.generated and len(req.generated) < 8
        resumed = req.prompt + req.generated
        r2 = eng.add_request(resumed, max_new_tokens=8 - len(req.generated))
        eng.step()
        hit = eng._active[r2].cached_prefix_tokens
        # everything the victim had fully written came back from the cache
        assert hit >= (len(resumed) - 1) // eng.bs * eng.bs
        out = eng.run()[r2]
        assert req.generated + out == full

    def test_int8_cache_quant_rejects_prefix_cache(self, model):
        with pytest.raises(ValueError, match="int8"):
            ServingEngine(model, cache_quant="int8", prefix_cache=True,
                          **ENGINE)
        # 'auto' degrades to off instead of erroring
        eng = ServingEngine(model, cache_quant="int8", **ENGINE)
        assert not eng.prefix_cache_enabled
        assert eng.cached_block_hashes() == set()

    def test_lru_eviction_under_pool_pressure_stays_correct(self, model):
        """A tight pool forces the reuse LRU to evict published blocks for
        fresh allocations; the eviction counter moves and every output
        stays correct."""
        eng = ServingEngine(model, max_batch_size=2, max_seq_len=32,
                            block_size=8, token_budget=8, num_blocks=4)
        prompts = [list(range(i * 20, i * 20 + 11)) for i in range(4)]
        for p in prompts:
            rid = eng.add_request(p, max_new_tokens=4)
            assert eng.run()[rid] == ref_greedy(model, p, 4)
        assert eng.prefix_evictions > 0
        assert eng.state_summary()["prefix_cache"]["evictions"] > 0


class TestFrontendPrefixAffinity:
    def test_routing_prefers_replica_with_cached_prefix(self, model):
        """After request 1 warms replica X's cache, request 2 with the
        same prefix must land on X even though the round-robin tie-break
        alone would rotate to the other replica."""
        engines = [ServingEngine(model, **ENGINE) for _ in range(2)]
        fe = ServingFrontend(engines)
        r1 = fe.submit(SHARED + [7, 9, 11], max_new_tokens=6)
        res1 = fe.run()
        warm = [e for e in engines if e.cached_block_hashes()]
        assert len(warm) == 1               # exactly one replica served r1
        r2 = fe.submit(SHARED + [5, 2], max_new_tokens=6)
        res2 = fe.run()
        assert res1[r1].ok and res2[r2].ok
        assert warm[0].prefix_hit_blocks == 2   # affinity beat round-robin
        assert res2[r2].tokens == ref_greedy(model, SHARED + [5, 2], 6)
        m = fe.metrics
        assert m.counter("prefix_hit_blocks_total") == 2
        assert m.counter("prefix_miss_blocks_total") >= 2
        assert 0 < m.gauge("prefix_cache_hit_rate") < 1
        assert "paddle_tpu_serving_prefix_cache_hit_rate" \
            in m.prometheus_text()


class TestMetricsMergePrefix:
    def test_merge_recomputes_fleet_hit_rate_from_counters(self):
        a, b = ServingMetrics(), ServingMetrics()
        a.inc("prefix_hit_blocks_total", 8)
        a.inc("prefix_miss_blocks_total", 2)
        a.set_gauge("prefix_cache_hit_rate", 0.8)
        b.inc("prefix_hit_blocks_total", 2)
        b.inc("prefix_miss_blocks_total", 8)
        b.set_gauge("prefix_cache_hit_rate", 0.2)
        a.inc("prefix_evictions_total", 3)
        m = ServingMetrics.merge([a.snapshot(), b.snapshot()])
        assert m["counters"]["prefix_hit_blocks_total"] == 10
        assert m["counters"]["prefix_miss_blocks_total"] == 10
        assert m["counters"]["prefix_evictions_total"] == 3
        # ratio recomputed from merged counters, not summed (1.0) or
        # averaged per-replica
        assert m["gauges"]["prefix_cache_hit_rate"] == pytest.approx(0.5)
