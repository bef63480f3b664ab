"""Serving control plane (ISSUE 2 tentpole): SLO-aware admission,
deadlines, recompute preemption, replica routing/failover, and live
metrics — ServingFrontend over ServingEngine replicas.

The acceptance-critical properties checked here:
* preempted-then-resumed requests produce tokens identical to an
  unpreempted greedy run (recompute preemption is lossless);
* with 2 replicas and one killed mid-flight, every admitted request
  either completes with correct greedy tokens on the survivor or returns
  a typed failure — none are silently dropped;
* deadline expiry is typed both mid-queue and mid-generation;
* ServingMetrics.snapshot()/prometheus_text() report non-trivial values.
"""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference import (
    Priority,
    RequestStatus,
    ServingEngine,
    ServingFrontend,
    ServingMetrics,
)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def model(serving_model):
    # the shared session-scoped sub-tiny model (tests/conftest.py,
    # ROADMAP item 6): one weight build for every serving test file.
    # The topology reset stays per-module — an earlier module may have
    # leaked a fleet group
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    return serving_model


def ref_greedy(model, prompt, n):
    from paddle_tpu.models.generation import generate

    ids = P.to_tensor(np.asarray(prompt, np.int32)[None, :])
    # the fixed-shape path (two programs): with growing caches every op of the
    # forward compiles again at every length, most of this reference's seconds
    out = generate(model, ids, max_new_tokens=n, do_sample=False,
                   use_static_cache=True)
    return list(np.asarray(out.numpy()).reshape(-1))


def make_engine(model, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("token_budget", 16)
    return ServingEngine(model, **kw)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestFrontendBasics:
    def test_multi_request_matches_generate(self, model):
        fe = ServingFrontend([make_engine(model)])
        p1, p2 = [3, 17, 101, 7, 250], [42, 5]
        r1 = fe.submit(p1, max_new_tokens=8)
        r2 = fe.submit(p2, max_new_tokens=4, priority=Priority.HIGH)
        res = fe.run()
        assert res[r1].ok and res[r1].tokens == ref_greedy(model, p1, 8)
        assert res[r2].ok and res[r2].tokens == ref_greedy(model, p2, 4)
        assert res[r1].ttft_s is not None and res[r1].e2e_s > 0

    def test_overloaded_typed_rejection(self, model):
        fe = ServingFrontend([make_engine(model)], max_queue_requests=2)
        rids = [fe.submit([3, 17], max_new_tokens=4) for _ in range(3)]
        r_over = fe.result(rids[2])
        assert r_over is not None
        assert r_over.status is RequestStatus.OVERLOADED
        assert "queue full" in r_over.detail
        # a request that can NEVER fit is rejected immediately too
        r_big = fe.result(fe.submit(list(range(1, 60)), max_new_tokens=30))
        assert r_big.status is RequestStatus.OVERLOADED
        assert "capacity" in r_big.detail
        res = fe.run()
        assert res[rids[0]].ok and res[rids[1]].ok
        assert fe.metrics.counter("rejected_overloaded_total") == 2

    def test_token_budget_admission_cap(self, model):
        fe = ServingFrontend([make_engine(model)], max_queue_tokens=30)
        r1 = fe.submit([3, 17, 101], max_new_tokens=8)   # 11 tokens
        r2 = fe.submit([42, 5], max_new_tokens=8)        # +10 = 21
        r3 = fe.submit([250, 4, 9], max_new_tokens=12)   # +15 > 30 -> shed
        assert fe.result(r3).status is RequestStatus.OVERLOADED
        res = fe.run()
        assert res[r1].ok and res[r2].ok

    def test_cancel_queued_and_running(self, model):
        # batch of 1 so the second request waits in the frontend queue
        fe = ServingFrontend([make_engine(model, max_batch_size=1)])
        r1 = fe.submit([3, 17, 101], max_new_tokens=10)
        r2 = fe.submit([42, 5], max_new_tokens=4)
        fe.step()
        fe.step()
        assert fe.cancel(r2)        # still queued
        assert fe.cancel(r1)        # running: evicted mid-generation
        assert not fe.cancel(r1)    # already resolved
        res = fe.run()
        assert res[r2].status is RequestStatus.CANCELLED
        assert res[r2].tokens == []
        assert res[r1].status is RequestStatus.CANCELLED
        full = ref_greedy(model, [3, 17, 101], 10)
        assert res[r1].tokens == full[:len(res[r1].tokens)]
        # eviction returned the blocks/slot
        eng = fe.replicas[0].engine
        assert eng.num_active == 0
        assert eng.blocks.num_free == eng.blocks.num_blocks


class TestDeadlines:
    def test_deadline_expiry_mid_queue(self, model):
        clock = FakeClock()
        fe = ServingFrontend([make_engine(model, max_batch_size=1)],
                             clock=clock)
        r1 = fe.submit([3, 17, 101], max_new_tokens=8)
        r2 = fe.submit([42, 5], max_new_tokens=4, deadline_s=1.0)
        fe.step()                      # r1 occupies the single slot
        clock.advance(2.0)             # r2's deadline passes while queued
        res = fe.run()
        assert res[r2].status is RequestStatus.DEADLINE_EXCEEDED
        assert res[r2].tokens == []
        assert "queued" in res[r2].detail
        assert res[r1].ok and res[r1].tokens == ref_greedy(model, [3, 17, 101], 8)
        assert fe.metrics.counter("shed_deadline_total") == 1

    def test_deadline_expiry_mid_generation(self, model):
        clock = FakeClock()
        fe = ServingFrontend([make_engine(model)], clock=clock)
        rid = fe.submit([3, 17, 101, 7], max_new_tokens=12, deadline_s=5.0)
        fe.step()   # prefill + first token
        fe.step()   # one megastep (K=8): 9 of 12 tokens — still running
        clock.advance(10.0)
        res = fe.run()
        r = res[rid]
        assert r.status is RequestStatus.DEADLINE_EXCEEDED
        assert "mid-generation" in r.detail
        # partial tokens are the greedy prefix, not garbage
        assert 0 < len(r.tokens) < 12
        full = ref_greedy(model, [3, 17, 101, 7], 12)
        assert r.tokens == full[:len(r.tokens)]
        # the evicted request's blocks came back
        eng = fe.replicas[0].engine
        assert eng.blocks.num_free == eng.blocks.num_blocks


class TestPreemption:
    def test_preemption_round_trip_token_parity(self, model):
        """Block-pool exhaustion evicts the LOW request for the HIGH one;
        once resumed (prompt+generated re-prefilled) its final tokens are
        identical to an unpreempted greedy run."""
        eng = make_engine(model, max_seq_len=32, num_blocks=4)
        fe = ServingFrontend([eng])
        plo = [3, 17, 101]                       # 3 + 8 = 11 -> 2 blocks
        rlo = fe.submit(plo, max_new_tokens=8, priority=Priority.LOW)
        # prefill + first token only: a second step would be a megastep
        # and finish all 8 tokens before the HIGH request ever arrives
        fe.step()
        assert len(fe._requests[rlo].generated) > 0
        phi = list(range(40, 50))                # 10 + 8 = 18 -> 3 blocks
        rhi = fe.submit(phi, max_new_tokens=8, priority=Priority.HIGH)
        res = fe.run()
        assert res[rhi].ok and res[rhi].tokens == ref_greedy(model, phi, 8)
        assert res[rlo].ok and res[rlo].tokens == ref_greedy(model, plo, 8)
        assert res[rlo].preemptions >= 1
        m = fe.metrics
        assert m.counter("preempted_total") >= 1
        assert m.counter("resumed_total") >= 1
        assert eng.blocks.num_free == eng.blocks.num_blocks

    def test_no_preemption_of_equal_or_higher_class(self, model):
        """A NORMAL arrival must not evict a running NORMAL sequence — it
        waits for natural retirement instead."""
        eng = make_engine(model, max_seq_len=32, num_blocks=4)
        fe = ServingFrontend([eng])
        r1 = fe.submit([3, 17, 101], max_new_tokens=8)
        for _ in range(3):
            fe.step()
        r2 = fe.submit(list(range(40, 50)), max_new_tokens=8)
        res = fe.run()
        assert res[r1].ok and res[r2].ok
        assert res[r1].preemptions == 0
        assert fe.metrics.counter("preempted_total") == 0

    def test_preemption_disabled(self, model):
        eng = make_engine(model, max_seq_len=32, num_blocks=4)
        fe = ServingFrontend([eng], preemption=False)
        rlo = fe.submit([3, 17, 101], max_new_tokens=8, priority=Priority.LOW)
        for _ in range(3):
            fe.step()
        rhi = fe.submit(list(range(40, 50)), max_new_tokens=8,
                        priority=Priority.HIGH)
        res = fe.run()
        assert res[rlo].ok and res[rhi].ok
        assert res[rlo].preemptions == 0


class TestFailover:
    def test_replica_kill_mid_generation(self, model):
        """Fault injection (acceptance criterion): 2 replicas, one dies
        mid-flight. Every admitted request either completes with correct
        greedy tokens on the survivor or returns a typed failure."""
        fe = ServingFrontend([make_engine(model), make_engine(model)])
        prompts = [[3, 17, 101], [42, 5, 7], [250, 4], [88, 13, 77]]
        rids = [fe.submit(p, max_new_tokens=6) for p in prompts]
        fe.step()   # prefill + first token; the next step's megastep
        doomed = fe.replicas[1]   # would retire everything (K=8 > 6)
        on_doomed = [fr.rid for fr in doomed.requests.values()]
        assert on_doomed, "routing should have spread load to replica 1"

        def boom():
            raise RuntimeError("injected replica failure")

        doomed.engine.step = boom
        res = fe.run()
        # NONE silently dropped: every rid has a typed result
        assert set(res) == set(rids)
        for rid, p in zip(rids, prompts):
            r = res[rid]
            assert r.status in (RequestStatus.COMPLETED, RequestStatus.FAILED)
            if r.ok:
                assert r.tokens == ref_greedy(model, p, 6)
        # the doomed replica's in-flight requests completed on the survivor
        for rid in on_doomed:
            assert res[rid].ok
        assert not doomed.alive and "injected" in doomed.last_error
        m = fe.metrics
        assert m.counter("replica_deaths_total") == 1
        assert m.counter("requeued_on_failover_total") == len(on_doomed)
        assert m.gauge("replicas_alive") == 1

    def test_all_replicas_dead_typed_failure(self, model):
        fe = ServingFrontend([make_engine(model)])
        rids = [fe.submit([3, 17, 101], max_new_tokens=6) for _ in range(3)]
        fe.step()

        def boom():
            raise RuntimeError("injected")

        fe.replicas[0].engine.step = boom
        res = fe.run()
        assert set(res) == set(rids)
        assert all(res[r].status is RequestStatus.FAILED for r in rids)
        # submits after total failure resolve immediately, typed
        r_late = fe.submit([5, 6], max_new_tokens=2)
        assert fe.result(r_late).status is RequestStatus.FAILED

    def test_least_loaded_routing_spreads_replicas(self, model):
        fe = ServingFrontend([make_engine(model), make_engine(model)])
        for i in range(4):
            fe.submit([3 + i, 17], max_new_tokens=4)
        fe.step()
        loads = [len(r.requests) for r in fe.replicas]
        assert loads == [2, 2], loads
        res = fe.run()
        assert all(r.ok for r in res.values())


class TestMetrics:
    def test_snapshot_and_prometheus_nontrivial(self, model):
        fe = ServingFrontend([make_engine(model)])
        p1, p2 = [3, 17, 101, 7], [42, 5]
        fe.submit(p1, max_new_tokens=8)
        fe.submit(p2, max_new_tokens=8)
        fe.run()
        snap = fe.metrics.snapshot()
        assert snap["counters"]["admitted_total"] == 2
        assert snap["counters"]["completed_total"] == 2
        assert snap["counters"]["tokens_emitted_total"] == 16
        assert snap["counters"]["engine_steps_total"] > 0
        assert snap["tokens_per_sec"] > 0
        lat = snap["latency"]
        assert lat["ttft_seconds"]["count"] == 2
        assert lat["ttft_seconds"]["p95"] >= lat["ttft_seconds"]["p50"] > 0
        assert lat["token_latency_seconds"]["count"] > 0
        assert lat["e2e_latency_seconds"]["count"] == 2
        # block utilization was sampled inside the loop and ends drained
        assert snap["gauges"]["blocks_capacity"] > 0
        assert snap["gauges"]["queue_depth"] == 0
        text = fe.metrics.prometheus_text()
        assert "# TYPE paddle_tpu_serving_admitted_total counter" in text
        assert "paddle_tpu_serving_admitted_total 2" in text
        assert "# TYPE paddle_tpu_serving_ttft_seconds summary" in text
        assert 'paddle_tpu_serving_ttft_seconds{quantile="0.95"}' in text
        assert "# TYPE paddle_tpu_serving_queue_depth gauge" in text
        assert text.endswith("\n")

    def test_registry_standalone(self):
        clock = FakeClock()
        m = ServingMetrics(clock=clock)
        m.inc("admitted_total", 3)
        m.set_gauge("queue_depth", 7)
        for v in (0.1, 0.2, 0.3, 0.4):
            m.observe("ttft_seconds", v)
        m.note_tokens(4, t=1.0)
        clock.advance(2.0)
        m.note_tokens(4, t=2.0)
        assert m.counter("tokens_emitted_total") == 8
        # steady-state rate: 4 tokens over the 1s first->last window
        assert m.tokens_per_sec() == pytest.approx(4.0)
        s = m.snapshot()
        assert s["latency"]["ttft_seconds"]["p50"] == pytest.approx(0.3)
        m.reset()
        assert m.counter("admitted_total") == 0
        assert m.tokens_per_sec() == 0.0


class TestEngineEvict:
    def test_evict_and_resume_token_parity(self, model):
        """Engine-level preemption contract: evict mid-generation, re-add
        prompt+generated, identical final stream."""
        eng = make_engine(model)
        prompt = [3, 17, 101, 7, 250]
        rid = eng.add_request(prompt, max_new_tokens=10)
        eng.step()   # prefill + first token
        eng.step()   # megastep: +8 -> 9 of 10, still active
        req = eng.evict(rid)
        assert req.generated and eng.num_active == 0
        assert eng.blocks.num_free == eng.blocks.num_blocks
        rid2 = eng.add_request(prompt + req.generated,
                               max_new_tokens=10 - len(req.generated))
        out = eng.run()
        full = ref_greedy(model, prompt, 10)
        assert req.generated + out[rid2] == full

    def test_evict_queued_and_unknown(self, model):
        eng = make_engine(model, max_batch_size=1)
        r1 = eng.add_request([3, 17], max_new_tokens=4)
        r2 = eng.add_request([42, 5], max_new_tokens=4)
        eng.step()                 # r1 admitted, r2 still queued
        req2 = eng.evict(r2)
        assert req2.rid == r2 and req2.blocks == []
        with pytest.raises(KeyError):
            eng.evict(999)
        out = eng.run()
        assert r1 in out and r2 not in out
