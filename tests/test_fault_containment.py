"""Fault containment for the serving fleet (ISSUE 7 tentpole): seeded
failpoint injection, per-request retry budgets + poison quarantine, the
respawn circuit breaker, transient-retry health probes, and brownout
degradation — all with FAST in-process fakes (no subprocess boots; the
full chaos soak lives in test_chaos_serving.py on the CI parallel
shard).

Acceptance-critical properties checked here:
* a deterministic poison request is quarantined (typed FAILED_POISON)
  after at most ``max_request_retries`` replica deaths, and the rest of
  the fleet keeps serving token-identical results;
* a crash-looping spawner opens the breaker instead of respawning
  unboundedly, half-open probes re-close it, and ``spawn_errors`` stays
  bounded;
* ``RpcTimeout`` during the cancel and deadline-shed evict paths fails
  over instead of crashing the control loop (CHANGES r8 regression);
* a replica that dies while ``draining=True`` is reaped exactly once —
  no double re-queue, accurate ``replica_deaths_total``;
* brownout sheds LOW typed, caps NORMAL, never touches HIGH, and
  recovers automatically through the hysteresis band.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.distributed.rpc import RpcTimeout
from paddle_tpu.inference import (
    AutoscalePolicy,
    BrownoutPolicy,
    FaultInjector,
    FaultSpec,
    Priority,
    RequestStatus,
    RespawnCircuitBreaker,
    ServingEngine,
    ServingFleet,
    ServingFrontend,
)
from paddle_tpu.inference.faults import (
    FaultyReplica,
    InjectedDrop,
    InjectedFault,
    InjectedTimeout,
    prompt_signature,
)
from paddle_tpu.inference.fleet import FleetAutoscaler, _BoundedErrors

pytestmark = pytest.mark.quick

ENGINE = dict(max_batch_size=2, max_seq_len=64, block_size=8,
              token_budget=16)


@pytest.fixture(scope="module")
def model(serving_model):
    # shared session-scoped sub-tiny model (tests/conftest.py, ROADMAP
    # item 6); topology reset stays per-module for leaked fleet groups
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


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------- injector
class TestFaultInjector:
    def test_unarmed_site_is_free_and_false(self):
        inj = FaultInjector({"engine.step": {"kind": "error"}})
        assert inj.fire("rpc.send") is False
        assert inj.total_fires == 0

    def test_after_times_and_counts(self):
        inj = FaultInjector(
            {"r0.step": {"kind": "error", "after": 2, "times": 2}},
            replica_namespaces=["r0"])
        assert inj.fire("r0.step") is False and inj.fire("r0.step") is False
        for _ in range(2):
            with pytest.raises(InjectedFault, match="failpoint 'r0.step'"):
                inj.fire("r0.step")
        assert inj.fire("r0.step") is False      # budget spent
        assert inj.fires("r0.step") == 2 and inj.kinds_fired() == ["error"]

    def test_seeded_probability_deterministic_per_site(self):
        def schedule(seed):
            inj = FaultInjector({"rpc.send": {"kind": "error", "p": 0.3}},
                                seed=seed)
            out = []
            for _ in range(64):
                try:
                    inj.fire("rpc.send")
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        assert schedule(7) == schedule(7)
        assert schedule(7) != schedule(8)
        assert 0 < sum(schedule(7)) < 64

    def test_sites_independent_of_interleaving(self):
        spec = {"ra.step": {"kind": "error", "p": 0.5},
                "rb.step": {"kind": "error", "p": 0.5}}

        def fires_of_a(interleave_b):
            inj = FaultInjector(spec, seed=3,
                                replica_namespaces=["ra", "rb"])
            out = []
            for _ in range(32):
                if interleave_b:
                    try:
                        inj.fire("rb.step")
                    except InjectedFault:
                        pass
                try:
                    inj.fire("ra.step")
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        # per-site RNGs: b's traversals must not perturb a's schedule
        assert fires_of_a(False) == fires_of_a(True)

    def test_match_gates_on_detail(self):
        inj = FaultInjector({"engine.step": {"kind": "error",
                                             "match": "p66-6-6-"}})
        assert inj.fire("engine.step", detail="p1-2-3-") is False
        # boundary anchoring: [66, 6, 61] must NOT match the poison
        assert inj.fire("engine.step", detail=prompt_signature([66, 6, 61])
                        ) is False
        with pytest.raises(InjectedFault):
            inj.fire("engine.step", detail="p4-5- p66-6-6-9-")
        assert prompt_signature([66, 6, 6, 9]) == "p66-6-6-9-"

    def test_kinds_timeout_drop_delay(self):
        class TypedTO(TimeoutError):
            pass

        inj = FaultInjector(
            {"rpc.send": {"kind": "timeout"}, "health.probe": {"kind": "drop"},
             "fleet.spawn": {"kind": "delay", "delay_s": 0.0}})
        with pytest.raises(TypedTO):
            inj.fire("rpc.send", timeout_exc=TypedTO)
        with pytest.raises(InjectedTimeout):
            inj.fire("rpc.send")
        with pytest.raises(InjectedDrop):
            inj.fire("health.probe")
        assert inj.fire("fleet.spawn") is True
        assert sorted(inj.kinds_fired()) == ["delay", "drop", "timeout"]

    def test_env_activation_round_trip(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_FAULTS", raising=False)
        assert FaultInjector.from_env() is None
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            '{"seed": 5, "sites": {"engine.step": {"kind": "error"}}}')
        inj = FaultInjector.from_env()
        assert inj is not None and inj.seed == 5
        assert inj.spec("engine.step").kind == "error"

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="kind"):
            FaultSpec(kind="explode")
        with pytest.raises(ValueError, match="p must be"):
            FaultSpec(kind="error", p=1.5)

    def test_unknown_site_rejected_at_arm_time(self, monkeypatch):
        import paddle_tpu.inference.faults as faults_mod

        # a typo'd site used to arm fine and then never fire — a chaos
        # schedule silently degrading to calm (ISSUE 11 satellite)
        with pytest.raises(ValueError, match="engine.stpe"):
            FaultInjector({"engine.stpe": {"kind": "error"}})
        # replica-scoped sites validate BOTH halves (ISSUE 12 satellite:
        # the r12-documented namespace hole is closed); isolate from
        # namespaces other tests registered process-wide
        monkeypatch.setattr(faults_mod, "REPLICA_NAMESPACES", set())
        with pytest.raises(ValueError, match="r0.stpe"):
            FaultInjector({"r0.stpe": {"kind": "error"}})
        with pytest.raises(ValueError, match="unregistered namespace"):
            FaultInjector({"r0.step": {"kind": "error"}})
        # the namespace typo whose op suffix is legal — the exact hole —
        # now raises instead of silently arming as a replica site
        with pytest.raises(ValueError, match="enigne"):
            FaultInjector({"enigne.step": {"kind": "error"}})
        FaultInjector({"r0.step": {"kind": "error"}},
                      replica_namespaces=["r0"])    # registered: fine

    def test_unknown_site_rejected_from_env_json(self, monkeypatch):
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            '{"sites": {"health.prob": {"kind": "error"}}}')
        with pytest.raises(ValueError, match="health.prob"):
            FaultInjector.from_env()

    def test_replica_namespace_env_and_registration_paths(self,
                                                          monkeypatch):
        """ISSUE 12 satellite: the namespace set is honored on every arm
        path — env JSON carries "replica_namespaces", and wrapping a
        FaultyReplica registers its own name for arm-after-wrap flows."""
        import paddle_tpu.inference.faults as faults_mod
        from paddle_tpu.inference.faults import register_replica_namespace

        monkeypatch.setattr(faults_mod, "REPLICA_NAMESPACES", set())
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            '{"sites": {"rz.step": {"kind": "error"}}}')
        with pytest.raises(ValueError, match="rz"):
            FaultInjector.from_env()
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            '{"sites": {"rz.step": {"kind": "error"}},'
            ' "replica_namespaces": ["rz"]}')
        inj = FaultInjector.from_env()
        assert inj.spec("rz.step").kind == "error"
        # module-level registration works for pre-planned names
        register_replica_namespace("ry")
        FaultInjector({"ry.evict": {"kind": "drop"}})
        # FaultyReplica registers its own name at construction
        class _E:  # noqa: N801 — minimal engine stand-in
            _active = {}
        FaultyReplica(_E(), FaultInjector({}), name="rw")
        FaultInjector({"rw.add_request": {"kind": "error"}})

    def test_run_scoped_namespace_registry(self, monkeypatch):
        """ISSUE 13 satellite: closes the r13-deferred scope hole — with
        a run-scoped registry handle, a later injector in the same
        process no longer validates against every name an earlier run
        registered (the stale copy-paste "r0.step" class)."""
        import paddle_tpu.inference.faults as faults_mod

        monkeypatch.setattr(faults_mod, "REPLICA_NAMESPACES", set())
        # run 1 registers its replica names in its own handle...
        run1: set = set()
        inj1 = FaultInjector({"r0.step": {"kind": "error"}},
                             replica_namespaces=["r0", "r1", "r2"],
                             namespace_registry=run1)
        assert inj1.spec("r0.step").kind == "error"
        assert run1 == {"r0", "r1", "r2"}
        # ...without polluting the process-global default
        assert faults_mod.REPLICA_NAMESPACES == set()
        # run 2, same process, fresh handle: the stale copy-paste site
        # now FAILS arm-time validation instead of silently arming
        # against run 1's registrations (and never firing)
        with pytest.raises(ValueError, match="unregistered namespace"):
            FaultInjector({"r0.step": {"kind": "error"}},
                          namespace_registry=set())
        # the global default path is equally isolated from run 1
        with pytest.raises(ValueError, match="unregistered namespace"):
            FaultInjector({"r0.step": {"kind": "error"}})

        # FaultyReplica inherits the injector's handle, so the
        # wrap-first-arm-later order stays coherent run-scoped too
        class _E:  # noqa: N801 — minimal engine stand-in
            _active = {}

        run3: set = set()
        inj3 = FaultInjector({}, namespace_registry=run3)
        FaultyReplica(_E(), inj3, name="rq")
        assert "rq" in run3
        assert "rq" not in faults_mod.REPLICA_NAMESPACES
        FaultInjector({"rq.evict": {"kind": "drop"}},
                      namespace_registry=run3)

    def test_register_failpoint_extends_registry(self):
        from paddle_tpu.inference.faults import (KNOWN_SITES,
                                                 register_failpoint)

        name = "testonly.flush"
        assert name not in KNOWN_SITES
        try:
            assert register_failpoint(name) == name
            inj = FaultInjector({name: {"kind": "error"}})
            with pytest.raises(InjectedFault):
                inj.fire(name)
        finally:
            KNOWN_SITES.discard(name)


# ----------------------------------------------------------------- breaker
class TestRespawnCircuitBreaker:
    def test_open_half_open_close_cycle(self):
        clk = FakeClock()
        br = RespawnCircuitBreaker(threshold=3, window_s=10.0,
                                   base_backoff_s=2.0, jitter=0.0, clock=clk)
        assert br.allow() and br.state == "closed"
        br.record_failure()
        br.record_failure()
        assert br.state == "closed" and br.allow()
        br.record_failure()
        assert br.state == "open" and br.open_count == 1
        assert not br.allow() and br.open_gauge == 1.0
        clk.advance(2.1)
        assert br.allow() and br.state == "half_open"
        assert not br.allow()            # exactly one probe
        br.record_failure()              # probe failed: doubled backoff
        assert br.state == "open" and br.open_count == 2
        clk.advance(3.9)
        assert not br.allow()
        clk.advance(0.2)
        assert br.allow() and br.state == "half_open"
        br.record_success()
        assert br.state == "closed" and br.allow() and br.open_gauge == 0.0

    def test_window_slides(self):
        clk = FakeClock()
        br = RespawnCircuitBreaker(threshold=3, window_s=5.0, jitter=0.0,
                                   clock=clk)
        br.record_failure()
        clk.advance(10.0)                # first failure ages out
        br.record_failure()
        br.record_failure()
        assert br.state == "closed"
        br.record_failure()
        assert br.state == "open"

    def test_jitter_bounded_and_seeded(self):
        def open_delay(seed):
            clk = FakeClock()
            br = RespawnCircuitBreaker(threshold=1, base_backoff_s=10.0,
                                       jitter=0.25, clock=clk, seed=seed)
            br.record_failure()
            return br._retry_at

        assert 7.5 <= open_delay(1) <= 12.5
        assert open_delay(4) == open_delay(4)        # seeded: reproducible
        seen = {round(open_delay(s), 6) for s in range(8)}
        assert len(seen) > 1                         # ...but actually jitters

    def test_backoff_capped(self):
        clk = FakeClock()
        br = RespawnCircuitBreaker(threshold=1, base_backoff_s=2.0,
                                   max_backoff_s=5.0, jitter=0.0, clock=clk)
        for _ in range(6):               # keep failing probes
            br.record_failure()
            clk.t = br._retry_at + 0.1
            assert br.allow()
        assert br._retry_at - clk.t <= 5.0 + 0.1


class TestFaultMetricsFlow:
    def test_new_counters_gauges_merge_and_fleet_page(self):
        """Acceptance criterion: the containment counters/gauges flow
        through ServingMetrics.merge() (counters summed, level/state
        gauges MAXED — two replicas at brownout 1 are not a fleet at 2)
        and render on the replica-labelled fleet scrape page.  They live
        in the frontend registry, so replica death cannot reset them —
        monotone by construction, no delta-fold needed."""
        from paddle_tpu.inference import ServingMetrics

        a, b = ServingMetrics(), ServingMetrics()
        a.inc("requests_retried_total", 3)
        a.inc("requests_quarantined_total", 1)
        a.inc("spawn_failures_total", 2)
        a.inc("breaker_open_total", 1)
        a.inc("shed_brownout_total", 4)
        b.inc("requests_retried_total", 2)
        a.set_gauge("degraded_mode", 1)
        b.set_gauge("degraded_mode", 2)
        a.set_gauge("respawn_breaker_open", 1.0)
        b.set_gauge("respawn_breaker_open", 0.0)
        m = ServingMetrics.merge({"w0": a.snapshot(), "w1": b.snapshot()})
        assert m["counters"]["requests_retried_total"] == 5
        assert m["counters"]["requests_quarantined_total"] == 1
        assert m["counters"]["spawn_failures_total"] == 2
        assert m["counters"]["breaker_open_total"] == 1
        assert m["counters"]["shed_brownout_total"] == 4
        assert m["gauges"]["degraded_mode"] == 2          # maxed
        assert m["gauges"]["respawn_breaker_open"] == 1.0  # maxed
        text = ServingMetrics.prometheus_text_fleet(
            {"frontend": a.snapshot(), "w1": b.snapshot()})
        assert ('paddle_tpu_serving_requests_quarantined_total'
                '{replica="frontend"} 1') in text
        assert ('paddle_tpu_serving_degraded_mode'
                '{replica="frontend"} 1') in text
        assert ('paddle_tpu_serving_respawn_breaker_open'
                '{replica="frontend"} 1') in text
        assert text.count("# TYPE paddle_tpu_serving_"
                          "requests_retried_total counter") == 1


class TestBoundedSpawnErrors:
    def test_ring_semantics(self):
        e = _BoundedErrors(maxlen=3)
        for i in range(5):
            e[f"w{i}"] = f"err{i}"
        assert len(e) == 3
        assert list(e) == ["w2", "w3", "w4"]     # oldest two fell off
        assert "w0" not in e and e["w4"] == "err4"
        e["w2"] = "updated"                      # refresh moves to newest
        e["w5"] = "err5"
        assert list(e) == ["w4", "w2", "w5"]
        assert e["w2"] == "updated"


# ------------------------------------------------- quarantine / retry budget
class TestPoisonQuarantine:
    def test_poison_quarantined_fleet_keeps_serving(self, model):
        """Acceptance criterion: a request that deterministically crashes
        whichever engine schedules it dies exactly max_request_retries+1
        replicas, resolves typed FAILED_POISON, and every other request
        completes token-identical on the survivors."""
        inj = FaultInjector({"engine.step": {"kind": "error",
                                             "match": "p66-6-6-"}})
        engines = [FaultyReplica(ServingEngine(model, **ENGINE), inj,
                                 name=f"r{i}") for i in range(4)]
        fe = ServingFrontend(engines, max_request_retries=2)
        poison = fe.submit([66, 6, 6], max_new_tokens=4)
        good = [fe.submit([3, 17, 101], max_new_tokens=6) for _ in range(3)]
        res = fe.run()
        pr = res[poison]
        assert pr.status is RequestStatus.FAILED_POISON
        assert pr.attempts == 3                 # retries + the final death
        assert "quarantined" in pr.detail
        m = fe.metrics
        assert m.counter("replica_deaths_total") == 3
        assert m.counter("requests_quarantined_total") == 1
        # the poison was retried max_request_retries times; co-located
        # requests re-queued by the same deaths count there too
        assert m.counter("requests_retried_total") >= 2
        assert (m.counter("requests_retried_total")
                == m.counter("requeued_on_failover_total"))
        assert sum(r.alive for r in fe.replicas) == 1
        for g in good:
            assert res[g].status is RequestStatus.COMPLETED
            assert res[g].tokens == ref_greedy(model, [3, 17, 101], 6)
        # the surviving fleet still accepts and serves new work
        late = fe.submit([5, 6, 7], max_new_tokens=4)
        res2 = fe.run()
        assert res2[late].tokens == ref_greedy(model, [5, 6, 7], 4)

    def test_zero_retry_budget_quarantines_first_death(self, model):
        inj = FaultInjector({"engine.step": {"kind": "error",
                                             "match": "p66-6-6-"}})
        fe = ServingFrontend(
            [FaultyReplica(ServingEngine(model, **ENGINE), inj, name=f"r{i}")
             for i in range(2)],
            max_request_retries=0)
        poison = fe.submit([66, 6, 6], max_new_tokens=4)
        res = fe.run()
        assert res[poison].status is RequestStatus.FAILED_POISON
        assert res[poison].attempts == 1
        assert fe.metrics.counter("replica_deaths_total") == 1
        assert fe.metrics.counter("requests_retried_total") == 0
        assert sum(r.alive for r in fe.replicas) == 1

    def test_transient_victim_within_budget_completes(self, model):
        """A request whose replica dies ONCE (not poison, just unlucky)
        is retried within budget and completes token-identical, with the
        attempt count surfaced in its result."""
        inj = FaultInjector({"r0.step": {"kind": "drop", "times": 1}},
                            replica_namespaces=["r0"])
        fe = ServingFrontend(
            [FaultyReplica(ServingEngine(model, **ENGINE), inj, name=f"r{i}")
             for i in range(2)],
            max_request_retries=2)
        rid = fe.submit([3, 17, 101], max_new_tokens=6)
        res = fe.run()
        assert res[rid].status is RequestStatus.COMPLETED
        assert res[rid].tokens == ref_greedy(model, [3, 17, 101], 6)
        assert res[rid].attempts == 1
        assert fe.metrics.counter("requests_quarantined_total") == 0
        assert fe.metrics.counter("requests_retried_total") == 1

    def test_first_terminal_state_wins(self, model):
        """A request quarantined inside _kill_replica during a cancel's
        evict fault keeps FAILED_POISON — the outer cancel path must not
        overwrite (or double-count) the terminal state."""
        fe = ServingFrontend([ServingEngine(model, **ENGINE)],
                             max_request_retries=0)
        rid = fe.submit([3, 17, 101], max_new_tokens=8)
        fe.step()
        rep = fe._requests[rid].replica
        assert rep is not None

        def boom(*a, **k):
            raise RpcTimeout("evict rpc timed out")

        rep.engine.evict = boom
        assert fe.cancel(rid)            # evict fault -> death -> quarantine
        res = fe.result(rid)
        assert res.status is RequestStatus.FAILED_POISON
        m = fe.metrics
        assert m.counter("requests_quarantined_total") == 1
        assert m.counter("cancelled_total") == 0
        assert fe.pending == 0


# ------------------------------------ RpcTimeout failover on evict paths
class TestRpcTimeoutEvictFailover:
    """CHANGES r8 says cancel/shed evict faults fail over instead of
    crashing; only the step path had a typed-RpcTimeout test.  These pin
    the contract with the exact exception a hung worker raises."""

    def test_cancel_rpc_timeout_fails_over_and_rescues_peer(self, model):
        fe = ServingFrontend([ServingEngine(model, **ENGINE),
                              ServingEngine(model, **ENGINE)])
        r1 = fe.submit([3, 17, 101], max_new_tokens=8)
        r2 = fe.submit([42, 5], max_new_tokens=6)
        fe.step()
        rep = fe._requests[r1].replica
        assert rep is not None

        def boom(*a, **k):
            raise RpcTimeout("rpc to 'worker0' timed out after 5s")

        rep.engine.evict = boom
        assert fe.cancel(r1)
        assert fe.result(r1).status is RequestStatus.CANCELLED
        assert not rep.alive and "timed out" in rep.last_error
        res = fe.run()
        assert res[r2].status is RequestStatus.COMPLETED
        assert res[r2].tokens == ref_greedy(model, [42, 5], 6)
        assert fe.metrics.counter("replica_deaths_total") == 1

    def test_deadline_shed_rpc_timeout_fails_over(self, model):
        clock = FakeClock()
        fe = ServingFrontend([ServingEngine(model, **ENGINE),
                              ServingEngine(model, **ENGINE)], clock=clock)
        r1 = fe.submit([3, 17, 101], max_new_tokens=8, deadline_s=5.0)
        r2 = fe.submit([42, 5], max_new_tokens=6)
        fe.step()
        rep1 = fe._requests[r1].replica

        def boom(*a, **k):
            raise RpcTimeout("rpc to 'worker0' timed out after 5s")

        rep1.engine.evict = boom
        clock.advance(10.0)
        res = fe.run()
        assert res[r1].status is RequestStatus.DEADLINE_EXCEEDED
        assert not rep1.alive
        assert res[r2].status is RequestStatus.COMPLETED
        assert res[r2].tokens == ref_greedy(model, [42, 5], 6)
        assert fe.metrics.counter("replica_deaths_total") == 1

    def test_dispatch_rpc_timeout_fails_over(self, model):
        fe = ServingFrontend([ServingEngine(model, **ENGINE),
                              ServingEngine(model, **ENGINE)])
        bad = fe.replicas[0].engine

        def boom(*a, **k):
            raise RpcTimeout("rpc to 'worker0' timed out after 60s")

        bad.add_request = boom
        rid = fe.submit([3, 17, 101], max_new_tokens=6)
        res = fe.run()
        assert res[rid].status is RequestStatus.COMPLETED
        assert res[rid].tokens == ref_greedy(model, [3, 17, 101], 6)
        assert fe.metrics.counter("replica_deaths_total") == 1
        # dispatch-path deaths charge the retry budget too
        assert res[rid].attempts == 1


# ---------------------------------------------------------------- brownout
class TestBrownout:
    def _frontend(self, model, **pol_kw):
        pol_kw.setdefault("queue_high", 2.0)
        pol_kw.setdefault("queue_low", 0.5)
        pol_kw.setdefault("enter_after", 2)
        pol_kw.setdefault("exit_after", 3)
        pol_kw.setdefault("normal_max_new_tokens", 3)
        return ServingFrontend(
            [ServingEngine(model, max_batch_size=1, max_seq_len=64,
                           block_size=8, token_budget=16)],
            brownout=BrownoutPolicy(**pol_kw), clock=FakeClock())

    def test_escalates_sheds_low_caps_normal_spares_high(self, model):
        fe = self._frontend(model)
        rids = [fe.submit([3 + i, 17], max_new_tokens=4) for i in range(6)]
        fe.step()
        fe.step()                      # sustained pressure -> level 1
        assert fe.brownout_level == 1
        assert fe.metrics.gauge("degraded_mode") == 1
        lo = fe.submit([9, 9], max_new_tokens=2, priority=Priority.LOW)
        out = fe.result(lo)
        assert out.status is RequestStatus.REJECTED_BROWNOUT
        assert "brownout level 1" in out.detail
        fe.step()
        fe.step()                      # still pressured -> level 2
        assert fe.brownout_level == 2
        cap = fe.submit([40, 41], max_new_tokens=10)          # NORMAL
        hi = fe.submit([50, 51], max_new_tokens=10,
                       priority=Priority.HIGH)                # untouched
        res = fe.run()
        assert len(res[cap].tokens) == 3
        assert "capped 10 -> 3" in res[cap].detail
        assert len(res[hi].tokens) == 10
        m = fe.metrics
        assert m.counter("shed_brownout_total") == 1
        assert m.counter("brownout_capped_total") == 1
        assert m.counter("brownout_transitions_total") == 2
        assert all(res[r].ok for r in rids)

    def test_recovers_automatically_when_pressure_clears(self, model):
        fe = self._frontend(model)
        for i in range(6):
            fe.submit([3 + i, 17], max_new_tokens=4)
        for _ in range(4):
            fe.step()
        assert fe.brownout_level == 2
        fe.run()
        for _ in range(8):             # idle control steps: hysteresis out
            fe.step()
        assert fe.brownout_level == 0
        assert fe.metrics.gauge("degraded_mode") == 0
        # LOW admission restored
        lo = fe.submit([9, 9], max_new_tokens=2, priority=Priority.LOW)
        assert fe.run()[lo].ok

    def test_hysteresis_band_holds_level(self, model):
        """Readings between the low and high thresholds must neither
        escalate nor de-escalate — that band is what stops flapping."""
        fe = self._frontend(model, queue_high=5.0, queue_low=1.0,
                            enter_after=1, exit_after=1)
        # one long runner pins the single batch slot, so the queue depth
        # is fully test-controlled (it cannot drain between steps)
        runner = fe.submit([2, 3], max_new_tokens=30)
        queued = [fe.submit([3 + i, 17], max_new_tokens=4) for i in range(6)]
        fe.step()                      # 6 queued / 1 replica > 5 -> level 1
        assert fe.brownout_level == 1
        for r in queued[:3]:           # drop INTO the band (1 < 3 <= 5)
            assert fe.cancel(r)
        for _ in range(4):             # band readings: level must hold
            fe.step()                  # even with exit_after=1
            assert fe.brownout_level == 1
        for r in queued[3:]:
            fe.cancel(r)
        fe.step()                      # queue empty: clear -> de-escalate
        assert fe.brownout_level == 0
        assert fe.run()[runner].ok

    def test_disabled_by_default_and_validated(self, model):
        fe = ServingFrontend([ServingEngine(model, **ENGINE)])
        for i in range(8):
            fe.submit([3 + i, 17], max_new_tokens=2)
        fe.run()
        assert fe.brownout_level == 0
        assert fe.metrics.counter("shed_brownout_total") == 0
        with pytest.raises(ValueError, match="hysteresis"):
            BrownoutPolicy(queue_low=9.0, queue_high=8.0)
        with pytest.raises(ValueError, match="normal_max_new_tokens"):
            BrownoutPolicy(normal_max_new_tokens=0)


# ------------------------------------------------ fleet: breaker + race
from paddle_tpu.inference import RemoteReplica  # noqa: E402


class FakeRemote(RemoteReplica):
    """RemoteReplica stand-in built on a real in-process engine: the
    frontend schedules against true engine state, while health/shutdown
    behave like RPC (raising once ``dead``).  Subclasses RemoteReplica so
    the fleet's isinstance-gated reap/heartbeat paths run, but never
    touches the rpc stack."""

    def __init__(self, engine, name):  # deliberately no super().__init__
        self._eng = engine
        self.worker = name
        self.rpc_timeout = 1.0
        self.dead = False

    def __getattr__(self, attr):
        return getattr(self._eng, attr)

    def _chk(self):
        if self.dead:
            raise ConnectionRefusedError(f"{self.worker} is dead")

    def begin_step(self):
        pass                           # no RPC to overlap

    def cached_block_hashes(self):
        return self._eng.cached_block_hashes()

    def add_request(self, *a, **k):
        self._chk()
        return self._eng.add_request(*a, **k)

    def step(self):
        self._chk()
        return self._eng.step()

    def evict(self, rid):
        self._chk()
        return self._eng.evict(rid)

    def pop_finished(self):
        return self._eng.pop_finished()

    def pop_token_logprobs(self):
        # the inherited RemoteReplica method reads the RPC mirror this
        # stand-in never initialises — read the engine directly
        return self._eng.pop_token_logprobs()

    def health(self, include_samples=False, timeout=None, retries=0,
               retry_backoff_s=0.0):
        self._chk()
        return {"state": self._eng.state_summary(), "metrics": {},
                "config": {}, "draining": False, "name": self.worker}

    def request_shutdown(self, timeout=None):
        self._chk()


def _stub_fleet(monkeypatch=None, clock=None, **kw):
    """A real ServingFleet with num_workers=0 (in-process KV master +
    rpc session, no subprocesses) — the harness the drain-race and
    breaker tests attach FakeRemotes / fake spawns to."""
    from paddle_tpu.distributed import rpc

    rpc.shutdown()                     # a leaked session would refuse init
    if clock is not None:
        kw["clock"] = clock
    return ServingFleet({"seed": 11}, num_workers=0, **kw)


class TestDrainHeartbeatRace:
    def test_replica_dying_while_draining_reaped_exactly_once(self, model):
        clock = FakeClock()
        fleet = _stub_fleet(clock=clock, heartbeat_interval_s=0.0)
        try:
            doomed = fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w0"))
            peer = fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w1"))
            fe = fleet.frontend
            rep0 = fe.replicas[0]
            rids = [fe.submit([3 + i, 17, 101], max_new_tokens=6)
                    for i in range(4)]
            fleet.step()   # prefill + first token (another step would
            clock.advance(1.0)   # megastep every request to completion)
            in_flight = len(rep0.requests)
            assert in_flight > 0
            fleet.drain_replica(rep0)
            doomed.dead = True         # dies WHILE draining
            clock.advance(1.0)
            fleet.step()               # heartbeat fails it; _reap removes it
            assert not rep0.alive
            assert rep0 not in fe.replicas
            m = fe.metrics
            assert m.counter("replica_deaths_total") == 1
            assert m.counter("requeued_on_failover_total") == in_flight
            # a second heartbeat+reap round must be a no-op (no double
            # death, no double re-queue, no double reap)
            clock.advance(1.0)
            fleet.step()
            assert m.counter("replica_deaths_total") == 1
            assert m.counter("requeued_on_failover_total") == in_flight
            assert len(fe.replicas) == 1 and fe.replicas[0].engine is peer
            # every re-queued request finishes on the survivor, correct
            deadline = 200
            while fe.pending and deadline:
                clock.advance(1.0)
                fleet.step()
                deadline -= 1
            res = fe.results()
            for i, rid in enumerate(rids):
                assert res[rid].status is RequestStatus.COMPLETED
                assert res[rid].tokens == ref_greedy(model,
                                                     [3 + i, 17, 101], 6)
        finally:
            fleet.shutdown()

    def test_drained_idle_replica_not_counted_dead(self, model):
        clock = FakeClock()
        fleet = _stub_fleet(clock=clock, heartbeat_interval_s=0.0)
        try:
            fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w0"))
            fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w1"))
            fe = fleet.frontend
            rep0 = fe.replicas[0]
            fleet.drain_replica(rep0)
            clock.advance(1.0)
            fleet.step()               # clean drain: reaped, not a death
            assert rep0 not in fe.replicas
            assert fe.metrics.counter("replica_deaths_total") == 0
            assert fe.metrics.counter("spawn_failures_total") == 0
        finally:
            fleet.shutdown()


class TestRespawnBreakerInFleet:
    def _crash_loop_fleet(self, monkeypatch, clock, breaker):
        """ServingFleet whose spawns always fail fast (the crash-looping
        worker config), with one live replica so the autoscaler sees
        pressure."""
        counter = {"n": 0}

        def fake_launch(self, name=None):
            counter["n"] += 1
            return name or f"wfail{counter['n']}"

        monkeypatch.setattr(ServingFleet, "_launch", fake_launch)

        def fail_registration(self, name):
            raise RuntimeError(f"worker '{name}' exited rc=1 before "
                               "registering")

        monkeypatch.setattr(ServingFleet, "_await_registration",
                            fail_registration)
        return _stub_fleet(clock=clock, spawn_breaker=breaker)

    def test_crash_loop_opens_breaker_and_bounds_respawns(
            self, model, monkeypatch):
        clock = FakeClock()
        breaker = RespawnCircuitBreaker(threshold=3, window_s=60.0,
                                        base_backoff_s=8.0, jitter=0.0,
                                        clock=clock)
        fleet = self._crash_loop_fleet(monkeypatch, clock, breaker)
        try:
            fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w0"))
            fe = fleet.frontend
            auto = FleetAutoscaler(fleet, AutoscalePolicy(
                min_workers=1, max_workers=4,
                scale_up_queue_per_replica=0.5, up_after=1, cooldown=0))
            for i in range(8):         # standing queue pressure
                fe.submit([3 + i, 17], max_new_tokens=4)

            spawned = 0
            for _ in range(20):        # a crash loop would spawn 20 here
                if auto.observe() == "up":
                    spawned += 1
                    # async spawn: wait for the boot thread to fail
                    for _ in range(100):
                        if not fleet.num_pending_spawns:
                            break
                        time.sleep(0.01)
                clock.advance(0.25)    # stays inside the 8 s backoff
            assert spawned == breaker.threshold      # bounded, not 20
            assert breaker.state == "open"
            assert "breaker:hold" in auto.actions
            assert len(fleet.spawn_errors) == breaker.threshold
            m = fe.metrics
            assert m.counter("spawn_failures_total") == breaker.threshold
            assert m.counter("breaker_open_total") == 1
            # backoff elapses -> ONE half-open probe, which fails and
            # re-opens with doubled backoff
            clock.advance(10.0)
            assert auto.observe() == "up"
            for _ in range(100):
                if not fleet.num_pending_spawns:
                    break
                time.sleep(0.01)
            assert breaker.state == "open" and breaker.open_count == 2
            assert auto.observe() == "hold"
            # the breaker state rides the scrape page (3 crash-loop
            # failures + the failed probe; opened twice)
            fleet.step()
            assert fe.metrics.gauge("respawn_breaker_open") == 1.0
            text = fe.metrics.prometheus_text()
            assert "paddle_tpu_serving_spawn_failures_total 4" in text
            assert "paddle_tpu_serving_breaker_open_total 2" in text
        finally:
            fleet.shutdown()

    def test_half_open_probe_success_recloses(self, model, monkeypatch):
        clock = FakeClock()
        breaker = RespawnCircuitBreaker(threshold=1, base_backoff_s=4.0,
                                        jitter=0.0, clock=clock)
        fleet = self._crash_loop_fleet(monkeypatch, clock, breaker)
        try:
            fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w0"))
            with pytest.raises(RuntimeError, match="before registering"):
                fleet.spawn_worker()               # blocking path feeds it
            assert breaker.state == "open"
            assert not breaker.allow()
            clock.advance(4.1)
            # the spawner is healthy again: half-open probe succeeds
            monkeypatch.setattr(
                ServingFleet, "_await_registration", lambda self, name: None)
            monkeypatch.setattr(
                ServingFleet, "_make_replica",
                lambda self, name: FakeRemote(ServingEngine(model, **ENGINE),
                                              name))
            assert breaker.allow()                 # the probe slot
            fleet.spawn_worker_async("w_probe")
            for _ in range(200):
                if not fleet.num_pending_spawns:
                    break
                time.sleep(0.01)
            fleet._attach_ready()
            # attaching is NOT yet success — a crash-looping worker also
            # attaches fine; the probe must SURVIVE early_death_s first
            assert breaker.state == "half_open"
            clock.advance(fleet.early_death_s + 0.1)
            fleet.step()                           # maturation sweep
            assert breaker.state == "closed"
            assert len(fleet.frontend.replicas) == 2
        finally:
            fleet.shutdown()

    def test_attach_then_early_death_loop_still_opens_breaker(self, model):
        """Code-review regression: a worker config that BOOTS AND
        ATTACHES fine but dies on first real work must still open the
        breaker — attach must not count as success (that would reset the
        failure window every cycle and the loop would respawn forever).
        Success is recorded only at maturation (alive past
        early_death_s)."""
        clock = FakeClock()
        breaker = RespawnCircuitBreaker(threshold=3, window_s=120.0,
                                        base_backoff_s=8.0, jitter=0.0,
                                        clock=clock)
        fleet = _stub_fleet(clock=clock, heartbeat_interval_s=0.0,
                            early_death_s=20.0, spawn_breaker=breaker)
        try:
            fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "stable"))
            clock.advance(21.0)
            fleet.step()               # 'stable' matures: one clean success
            assert breaker.state == "closed"
            for i in range(3):         # boots-fine-dies-early crash loop
                doomed = fleet._attach_replica(
                    FakeRemote(ServingEngine(model, **ENGINE), f"loop{i}"))
                clock.advance(2.0)     # well inside early_death_s
                doomed.dead = True
                fleet.step()
            assert breaker.state == "open"
            assert not breaker.allow()
            assert fleet.frontend.metrics.counter(
                "spawn_failures_total") == 3
        finally:
            fleet.shutdown()

    def test_early_death_counts_as_spawn_failure(self, model):
        clock = FakeClock()
        fleet = _stub_fleet(clock=clock, heartbeat_interval_s=0.0,
                            early_death_s=20.0)
        try:
            doomed = fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w0"))
            fleet._attach_replica(
                FakeRemote(ServingEngine(model, **ENGINE), "w1"))
            fe = fleet.frontend
            clock.advance(5.0)         # dies 5s after attach: early
            doomed.dead = True
            fleet.step()
            assert fe.metrics.counter("spawn_failures_total") == 1
            assert "early death" in fleet.spawn_errors["w0"]
            assert len(fleet.spawn_breaker._failures) == 1
            # a LATE death (past early_death_s) is a plain replica death
            survivor = fe.replicas[0]
            clock.advance(100.0)
            survivor.engine.dead = True
            fleet.step()
            assert fe.metrics.counter("spawn_failures_total") == 1
            assert fe.metrics.counter("replica_deaths_total") == 2
        finally:
            fleet.shutdown()


# ----------------------------------------------- transient health retries
class TestHealthProbeTransientRetry:
    def test_single_transport_blip_does_not_fail_over(self, model):
        """One injected rpc timeout on the health probe is absorbed by
        the retry; a persistent fault still raises (and would fail over).
        Uses a real loopback rpc session, like TestRpcTimeoutSurface."""
        from paddle_tpu.distributed import rpc
        from paddle_tpu.inference import RemoteReplica, fleet as fleet_mod

        rpc.shutdown()
        engine = ServingEngine(model, **ENGINE)
        fleet_mod.init_worker(engine, name="self_probe")
        rpc.init_rpc("self_probe", rank=0, world_size=1)
        try:
            rep = RemoteReplica("self_probe", rpc_timeout=5.0)
            # one blip: first probe attempt times out, retry succeeds
            rpc.set_fault_injector(FaultInjector(
                {"rpc.send": {"kind": "timeout", "match": "_w_health",
                              "times": 1}}))
            h = rep.health(retries=1, retry_backoff_s=0.0)
            assert h["name"] == "self_probe"
            # persistent fault: retries exhausted -> typed RpcTimeout
            rpc.set_fault_injector(FaultInjector(
                {"rpc.send": {"kind": "timeout", "match": "_w_health"}}))
            with pytest.raises(RpcTimeout):
                rep.health(retries=2, retry_backoff_s=0.0)
            # data-plane step stays fail-fast: no retry absorbs its fault
            rpc.set_fault_injector(FaultInjector(
                {"rpc.send": {"kind": "timeout", "match": "_w_step",
                              "times": 1}}))
            with pytest.raises(RpcTimeout):
                rep.step()
        finally:
            rpc.set_fault_injector(None)
            rpc.shutdown()


class TestRpcEnvFailpoint:
    def test_env_gates_lazy_arming(self, monkeypatch):
        """No env spec -> no injector AND no import of the jax-heavy
        inference package from an rpc-only process; with the spec set,
        the 'rpc.send' site arms from the env."""
        from paddle_tpu.distributed import rpc

        monkeypatch.setattr(rpc, "_fault_env_checked", False)
        monkeypatch.setattr(rpc, "_fault_injector", None)
        monkeypatch.delenv("PADDLE_TPU_FAULTS", raising=False)
        assert rpc._get_fault_injector() is None
        monkeypatch.setattr(rpc, "_fault_env_checked", False)
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            '{"sites": {"rpc.send": {"kind": "timeout"}}}')
        inj = rpc._get_fault_injector()
        assert inj is not None and inj.spec("rpc.send").kind == "timeout"


# ----------------------------------------------------- engine failpoint
class TestEngineFailpoint:
    def test_constructor_injector_fires_in_step(self, model):
        inj = FaultInjector({"engine.step": {"kind": "error", "after": 1}})
        eng = ServingEngine(model, fault_injector=inj, **ENGINE)
        eng.add_request([3, 17, 101], max_new_tokens=4)
        eng.step()                       # after=1 spares the first step
        with pytest.raises(InjectedFault):
            eng.step()
        assert inj.fires("engine.step") == 1

    def test_env_injector_scoped_to_engine(self, model, monkeypatch):
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            '{"sites": {"engine.step": {"kind": "error"}}}')
        eng = ServingEngine(model, **ENGINE)
        eng.add_request([3, 17], max_new_tokens=2)
        with pytest.raises(InjectedFault):
            eng.step()
        monkeypatch.delenv("PADDLE_TPU_FAULTS")
        clean = ServingEngine(model, **ENGINE)
        assert clean._faults is None
        rid = clean.add_request([3, 17], max_new_tokens=2)
        assert clean.run()[rid] == ref_greedy(model, [3, 17], 2)
