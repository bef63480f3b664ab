"""Multi-tenant elastic serving platform (ISSUE 18): warm-worker pool,
zero-downtime rolling weight swaps, and N models/adapters behind one
frontend — TenantRegistry/WarmPool over the serving control plane.

Acceptance-critical properties checked here:
* a warm-boot pre-compile (the ``--warm`` worker's throwaway request)
  leaves the engine token- AND cache-identical to a cold boot — warm
  attach changes no serving behavior, only the time-to-capacity;
* ``rolling_swap`` across a 3-replica frontend drops zero admitted
  requests, and every request completing on one weights version is
  token-identical (greedy and seeded) to a single-engine run of that
  version, with the version label fenced onto each result;
* per-tenant token budgets isolate a bursty tenant from a steady one
  (typed OVERLOADED rejection, budget released at completion);
* tenant-aware routing serves a tenant's OWN model by swapping an idle
  replica on demand — where naive round-robin placement would have
  produced wrong-model tokens;
* the warm pool consults the respawn breaker (a crash-looping warm
  spawn must not refill forever) and survives an armed ``pool.attach``
  fault by re-pooling the worker.

The one real-process test (a fleet with ``warm_pool_size=1`` claiming
its pre-booted worker) is marked slow, same budget note as
test_serving_fleet.py.
"""
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference import (
    FaultInjector,
    RequestStatus,
    ServingEngine,
    ServingFrontend,
    TenantRegistry,
    TenantSpec,
    WarmPool,
)

pytestmark = pytest.mark.quick

ENGINE = dict(max_batch_size=2, max_seq_len=64, block_size=8,
              token_budget=16)

PROMPTS = [[3, 17, 101, 7, 250], [42, 5], [250, 4, 9], [88, 13, 77]]


@pytest.fixture(scope="module")
def model(serving_model):
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    return serving_model


@pytest.fixture(scope="module")
def model_v2():
    # a second same-geometry model (different seed => different weights):
    # the swap/routing target.  Geometry must match — load_weights bakes
    # the attention shape into the compiled programs
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    set_hybrid_communicate_group(None)
    P.seed(13)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        num_hidden_layers=1, num_attention_heads=2,
        max_position_embeddings=256))
    m.eval()
    return m


def ref_greedy(model, prompt, n):
    from paddle_tpu.models.generation import generate

    ids = P.to_tensor(np.asarray(prompt, np.int32)[None, :])
    # the fixed-shape path (two programs): with growing caches every op of the
    # forward compiles again at every length, most of this reference's seconds
    out = generate(model, ids, max_new_tokens=n, do_sample=False,
                   use_static_cache=True)
    return list(np.asarray(out.numpy()).reshape(-1))


def make_engine(model, **kw):
    merged = dict(ENGINE)
    merged.update(kw)
    return ServingEngine(model, **merged)


def warm_up(engine):
    """The exact pre-compile a ``--warm`` worker runs before registering
    (tools/serving_worker.py): one throwaway sub-block request."""
    engine.add_request([1], max_new_tokens=2)
    while engine.num_active or engine._queue:
        engine.step()
    engine.pop_finished()


class TestWarmBootParity:
    def test_warm_precompile_is_cold_boot_equivalent(self, model):
        """The warm-up request must leave NO trace a request could
        observe: empty prefix cache (its prompt is shorter than a
        block, so no FULL block was ever published) and token-identical
        serving afterwards."""
        warm = make_engine(model)
        warm_up(warm)
        assert warm.blocks.cached_hashes() == set(), (
            "warm-up published prefix-cache blocks — a warm attach "
            "would diverge from a cold boot on cache hits")
        assert warm.num_active == 0 and not warm._queue

        cold = make_engine(model)
        outs = []
        for eng in (warm, cold):
            rids = [eng.add_request(list(p), max_new_tokens=5)
                    for p in PROMPTS[:2]]
            done = {}
            while eng.num_active or eng._queue:
                eng.step()
                done.update(eng.pop_finished())
            outs.append([done[r] for r in rids])
        assert outs[0] == outs[1], (
            "warm-booted engine diverged from a cold boot")


class TestRollingSwap:
    def test_zero_drop_and_version_fenced_parity(self, model, model_v2):
        """Admitted requests ride through a 3-replica rolling swap
        untouched: zero drops, and each result carries the version it
        completed on with greedy AND seeded token parity against a
        single-engine run of that exact version."""
        fe = ServingFrontend([make_engine(model) for _ in range(3)])
        pre = [fe.submit(list(p), max_new_tokens=5) for p in PROMPTS]
        pre_seeded = fe.submit([9, 33, 2], max_new_tokens=5,
                               temperature=0.8, top_k=8, seed=5)
        for _ in range(2):
            fe.step()           # get traffic decoding on v0 mid-swap
        swapped = fe.rolling_swap(model_v2, "v2")
        assert swapped == 3
        assert fe.metrics.counter("weight_swaps_total") == 3
        post = [fe.submit(list(p), max_new_tokens=5) for p in PROMPTS]
        post_seeded = fe.submit([9, 33, 2], max_new_tokens=5,
                                temperature=0.8, top_k=8, seed=5)
        res = fe.run()
        assert all(r.status is RequestStatus.COMPLETED for r in res.values())

        # single-version references, one engine each, same sampling
        refs = {}
        for label, m in (("v0", model), ("v2", model_v2)):
            one = ServingFrontend([make_engine(m)])
            g = [one.submit(list(p), max_new_tokens=5) for p in PROMPTS]
            s = one.submit([9, 33, 2], max_new_tokens=5,
                           temperature=0.8, top_k=8, seed=5)
            r1 = one.run()
            refs[label] = ([r1[x].tokens for x in g], r1[s].tokens)
        # a request queued at swap time may legitimately land on an
        # already-swapped replica — the guarantee is that every request
        # completes on ONE version and matches THAT version's reference
        for rid, i in zip(pre, range(len(PROMPTS))):
            v = res[rid].weights_version
            assert v in ("v0", "v2")
            assert res[rid].tokens == refs[v][0][i]
        assert res[pre_seeded].tokens == \
            refs[res[pre_seeded].weights_version][1]
        # traffic decoding when the swap began drained on its v0 replica
        assert any(res[r].weights_version == "v0"
                   for r in pre + [pre_seeded])
        # everything submitted after the swap serves v2, version-fenced
        for rid, i in zip(post, range(len(PROMPTS))):
            assert res[rid].weights_version == "v2"
            assert res[rid].tokens == refs["v2"][0][i]
        assert res[post_seeded].weights_version == "v2"
        assert res[post_seeded].tokens == refs["v2"][1]

    def test_swap_fault_keeps_old_version_serving(self, model, model_v2):
        """An armed weights.swap fault pins that replica to its OLD
        version — typed failure counter, no half-swapped state."""
        inj = FaultInjector({"weights.swap": {"kind": "error", "times": 1}},
                            seed=0)
        engines = [ServingEngine(model, fault_injector=inj, **ENGINE),
                   ServingEngine(model, fault_injector=inj, **ENGINE)]
        fe = ServingFrontend(engines)
        assert fe.rolling_swap(model_v2, "v2") == 1
        assert fe.metrics.counter("weight_swap_failures_total") == 1
        versions = sorted(e.weights_version for e in engines)
        assert versions == ["v0", "v2"]
        rid = fe.submit(PROMPTS[0], max_new_tokens=4)
        res = fe.run()
        ref = ref_greedy(model if res[rid].weights_version == "v0"
                         else model_v2, PROMPTS[0], 4)
        assert res[rid].tokens == ref


class TestTenantIsolation:
    def test_budget_rejects_typed_and_releases_on_completion(self, model):
        reg = TenantRegistry([TenantSpec("steady"),
                              TenantSpec("bursty", token_budget=10)])
        fe = ServingFrontend([make_engine(model)], tenants=reg)
        ok = fe.submit([5, 6], max_new_tokens=4, tenant="bursty")   # cost 6
        rej = fe.submit([5, 6, 7], max_new_tokens=4, tenant="bursty")
        assert ok >= 0 and rej < 0
        assert fe.result(rej).status is RequestStatus.OVERLOADED
        assert fe.metrics.counter("tenant_rejected_budget_total") == 1
        # the steady tenant is untouched by bursty's backpressure
        st = fe.submit(PROMPTS[0], max_new_tokens=4, tenant="steady")
        assert st >= 0
        res = fe.run()
        assert res[ok].status is RequestStatus.COMPLETED
        assert res[ok].tenant == "bursty"
        # completion released the budget: the same request admits now
        again = fe.submit([5, 6, 7], max_new_tokens=4, tenant="bursty")
        assert again >= 0
        assert fe.run()[again].status is RequestStatus.COMPLETED
        snap = reg.snapshot()
        assert snap["bursty"]["served"] > 0 and snap["steady"]["served"] > 0


class TestTenantRouting:
    def test_routes_to_tenant_model_where_round_robin_would_not(
            self, model, model_v2):
        """Tenant "a" owns model m2.  Naive round-robin would place its
        request on a default-model replica and return default-model
        tokens; tenant-aware routing swaps an idle replica to m2 first
        and the tokens prove which weights actually served."""
        reg = TenantRegistry([TenantSpec("a", model_id="m2")],
                             model_provider={"m2": model_v2}.get)
        engines = [make_engine(model), make_engine(model)]
        fe = ServingFrontend(engines, tenants=reg)
        rid = fe.submit(PROMPTS[1], max_new_tokens=5, tenant="a")
        res = fe.run()
        want = ref_greedy(model_v2, PROMPTS[1], 5)
        wrong = ref_greedy(model, PROMPTS[1], 5)
        assert want != wrong, "seed-11 vs seed-13 models must disagree"
        assert res[rid].tokens == want
        assert fe.metrics.counter("tenant_routing_hits_total") >= 1
        assert fe.metrics.counter("weight_swaps_total") == 1
        # exactly one replica swapped; the other still serves the default
        assert sorted(e.model_id for e in engines) == ["default", "m2"]


class TestWarmPool:
    def test_breaker_gates_refill_on_crash_looping_spawn(self):
        from paddle_tpu.inference import RespawnCircuitBreaker

        br = RespawnCircuitBreaker(threshold=2, window_s=100.0,
                                   base_backoff_s=50.0, clock=lambda: 0.0)

        def bad_spawn(name):
            raise RuntimeError("worker died at boot")

        pool = WarmPool(2, bad_spawn, breaker=br)
        pool.refill()
        pool.refill()
        assert not br.allow(), "two boot failures must open the breaker"
        assert pool.depth() == 0
        pool.refill()            # breaker open: no spawn attempted
        assert pool.depth() == 0

    def test_attach_fault_repools_and_generation_fences(self):
        inj = FaultInjector({"pool.attach": {"kind": "error", "times": 1}},
                            seed=0)
        pool = WarmPool(1, lambda name: f"h-{name}", fault_injector=inj)
        pool.refill()
        assert pool.depth() == 1
        assert pool.claim() is None      # armed fault: claim fails...
        assert pool.depth() == 1         # ...but the worker is re-pooled
        name, handle = pool.claim()
        assert handle == f"h-{name}"
        # generation fence: a worker still BOOTING when the inventory is
        # drained (e.g. a rolling swap — it would boot stale weights) has
        # its late note_ready refused
        booting = WarmPool(1, lambda name: None)   # async spawn contract
        booting.refill()
        assert booting.depth() == 1                # pending, not ready
        assert booting.drain_ready() == []         # bumps the generation
        assert booting.note_ready("warm0", "h") is False
        assert booting.depth() == 0


@pytest.mark.slow
class TestFleetWarmPool:
    def test_warm_worker_claimed_on_scale_up(self):
        """A fleet with warm_pool_size=1 pre-boots a spare; scale-up
        claims it and attaches via a health probe instead of a ~10 s
        boot — and the attached replica serves with greedy parity."""
        from paddle_tpu.distributed import rpc
        from paddle_tpu.inference import ServingFleet
        from tests.test_serving_fleet import SPEC, _local_model

        rpc.shutdown()
        fleet = ServingFleet(SPEC, num_workers=1, warm_pool_size=1,
                             heartbeat_interval_s=0.5, spawn_timeout=180.0)
        try:
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                with fleet.warm_pool._lock:
                    if fleet.warm_pool._ready:
                        break
                time.sleep(0.2)
            else:
                pytest.fail("warm worker never became ready")
            t0 = time.monotonic()
            name = fleet.spawn_worker_async()
            while fleet.num_pending_spawns and time.monotonic() - t0 < 60:
                fleet.step()
                time.sleep(0.05)
            attach_s = time.monotonic() - t0
            assert fleet.num_pending_spawns == 0 and not fleet.spawn_errors
            assert len(fleet.frontend.replicas) == 2
            assert attach_s < 30, f"warm attach took {attach_s:.1f}s"
            assert fleet.frontend.metrics.counter("pool_attaches_total") == 1
            rid = fleet.frontend.submit(PROMPTS[0], max_new_tokens=4)
            res = fleet.run()
            assert res[rid].ok
            assert res[rid].tokens == ref_greedy(_local_model(),
                                                 PROMPTS[0], 4)
            assert name not in fleet.spawn_errors
        finally:
            fleet.shutdown()
