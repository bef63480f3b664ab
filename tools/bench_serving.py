#!/usr/bin/env python
"""Serving control-plane benchmark: open-loop arrivals through the
ServingFrontend (ISSUE 2 satellite; reference analog: the serving-stack
QPS/latency harnesses around block_multihead_attention decode).

Open-loop means arrival times are drawn up front from a seeded Poisson
process and submitted when the wall clock passes them, INDEPENDENT of
service progress — so the bench measures how the frontend behaves under
offered load (queueing, shedding, TTFT growth), not a closed feedback
loop that politely waits for capacity.

Reports steady-state decode tokens/s (from the metrics registry's
first->last emission window, which excludes compile/prefill lead-in) and
p50/p95 TTFT across completed requests.  One JSON line on stdout — the
same schema bench_ladder.py rungs use, so the ladder imports and re-emits
``run_bench()`` directly.

``--workers N`` switches to REMOTE mode (ISSUE 3): the same open-loop
workload through a ServingFleet of N serving_worker.py processes behind
the RPC stack instead of in-process replicas — what the fleet ladder
rung measures (per-step HTTP round trips are the cost being watched).

``--shared-prefix-len S`` switches to the PREFIX-CACHE workload
(ISSUE 5): every request's prompt opens with the same S-token system
prompt (S ≥ 2 blocks).  The same request stream runs cache-off then
cache-on; the report carries the prefix hit rate, prefill tokens
actually computed in both modes (the gated ``value`` is their ratio —
deterministic counters, not wall clock), per-mode TTFT, and asserts the
greedy outputs are token-identical.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.device import on_tpu  # noqa: E402
from paddle_tpu.jit import use_compile_cache  # noqa: E402


def _refuse_workers_on_chip(mode):
    """A chip belongs to one process, and this one initialised JAX to pick
    its preset, so it holds every chip of the host: a worker process that
    wanted one would fail or hang. CPU workers under a chip-sized preset
    would be a CPU run reported as the chip's."""
    if on_tpu():
        sys.exit(f"bench_serving {mode}: worker processes each need a chip "
                 "of their own and this process already holds the host's. "
                 "Drive several replicas from one process with --replicas N, "
                 "or run this mode with JAX_PLATFORMS=cpu.")


def _workload(seed, num_requests, rate_rps):
    """Shared config for local and remote mode: model/engine spec, seeded
    prompts, Poisson arrival times."""
    import jax
    import numpy as np

    backend = jax.default_backend()
    on_accel = on_tpu()
    if on_accel:
        model = dict(vocab_size=32000, hidden_size=2560,
                     intermediate_size=8192, num_hidden_layers=9,
                     num_attention_heads=10,
                     max_position_embeddings=2048, dtype="bfloat16")
        engine = dict(max_batch_size=8, max_seq_len=448, block_size=64,
                      token_budget=64, num_blocks=24)
        prompt_lens, max_new = (96, 160, 224), 32
        num_requests = num_requests or 32
        rate_rps = rate_rps or 16.0
    else:
        model = dict(vocab_size=512, hidden_size=128,
                     intermediate_size=352, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=256)
        engine = dict(max_batch_size=4, max_seq_len=64, block_size=8,
                      token_budget=16, num_blocks=8)
        # pool binds before slots: preemption pressure
        prompt_lens, max_new = (4, 8, 12), 8
        num_requests = num_requests or 48
        # ~4x service rate so a queue must form: megastep decode (r11)
        # lifted the service rate past the old 200 rps offered load —
        # the rung was arrival-limited and measured the Poisson schedule,
        # not the frontend (rate_rps/num_requests are perf_gate identity
        # keys, so this re-baselines loudly)
        rate_rps = rate_rps or 800.0
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model["vocab_size"],
                           (int(rng.choice(prompt_lens)),)).tolist()
               for _ in range(num_requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, num_requests))
    return (backend, on_accel, model, engine, prompts, arrivals, max_new,
            num_requests, rate_rps)


def _drive(fe, step, prompts, arrivals, max_new, warm_n, after_warm=None):
    """Warm the compiled step programs, then replay the open-loop arrival
    schedule through ``fe`` (stepping via ``step()``).  ``after_warm``
    runs right after the frontend registry reset — the fleet mode uses it
    to reset the per-worker registries too, so every reported counter
    covers the same measured window."""
    from paddle_tpu.inference import Priority

    # Two staggered warm waves: wave 1 gets extra decode budget so it is
    # still mid-generation when wave 2's prompts land — a row prefilling
    # while another decodes is exactly what arms the MIXED-phase megastep
    # program (ISSUE 16), so its compile must happen here and not inside
    # the measured window (on this CPU container that compile is ~10x the
    # whole measured workload).  Wave 2 uses the measured max_new and
    # drains to completion, covering the pure-decode scan's tail K
    # buckets the same way the old single-wave warm did.
    warm = [fe.submit(prompts[0], max_new_tokens=max_new + 24)
            for _ in range(warm_n)]
    guard = 0
    while fe.pending and guard < 10_000:
        step()
        guard += 1
        snap = fe.metrics.snapshot()
        if snap["latency"]["ttft_seconds"]["count"] >= warm_n:
            break  # every wave-1 row is past prefill and decoding
    warm += [fe.submit(prompts[0], max_new_tokens=max_new)
             for _ in range(warm_n)]
    while fe.pending:
        step()
    assert all(fe.result(w).ok for w in warm)
    fe.metrics.reset()
    if after_warm is not None:
        after_warm()

    n = len(prompts)
    priorities = [Priority.HIGH if i % 4 == 0 else Priority.NORMAL
                  for i in range(n)]
    t0 = time.monotonic()
    submitted = 0
    rids = []
    while fe.pending or submitted < n:
        now = time.monotonic() - t0
        while submitted < n and arrivals[submitted] <= now:
            rids.append(fe.submit(prompts[submitted], max_new_tokens=max_new,
                                  priority=priorities[submitted]))
            submitted += 1
        step()
    return rids, time.monotonic() - t0


def _report(metric, fe, rids, wall_s, extra):
    import bench_ladder  # repo root is on sys.path (top of this file)

    res = fe.results()
    snap = fe.metrics.snapshot()
    completed = [res[r] for r in rids if res[r].ok]
    # TTFT percentiles come from the metrics registry itself (every
    # first-token event this run — all requests completed, so identical
    # population to a completed-only view); inter-token latency is the
    # token_latency_seconds series, i.e. per-token time between harvest
    # boundaries (a megastep's K-token burst amortizes over the burst)
    ttft = snap["latency"]["ttft_seconds"]
    itl = snap["latency"]["token_latency_seconds"]
    out = {
        "host": bench_ladder.host_fingerprint(),
        "p50_ttft_ms": round(ttft["p50"] * 1e3, 1),
        "p95_ttft_ms": round(ttft["p95"] * 1e3, 1),
        "p50_itl_ms": round(itl["p50"] * 1e3, 2),
        "p95_itl_ms": round(itl["p95"] * 1e3, 2),
        "megasteps": snap["counters"]["megasteps_total"],
        "completed": len(completed),
        "shed_deadline": snap["counters"]["shed_deadline_total"],
        "rejected_overloaded":
            snap["counters"]["rejected_overloaded_total"],
        "preempted": snap["counters"]["preempted_total"],
        "peak_queue_depth": snap["gauges"]["queue_depth_peak"],
        "peak_block_pool_utilization":
            round(snap["gauges"]["block_pool_utilization_peak"], 3),
        "engine_steps": snap["counters"]["engine_steps_total"],
        "wall_s": round(wall_s, 2),
        "method": "open-loop Poisson arrivals; tokens/s from the "
                  "metrics registry's first->last emission window; "
                  "two-wave staggered warm (arms the mixed-phase "
                  "megastep program before the window)",
    }
    out.update(extra)
    return {
        "metric": metric,
        "value": round(snap["tokens_per_sec"], 1),
        "unit": "tokens/s",
        "extra": out,
    }


def run_bench(num_requests=None, rate_rps=None, replicas=1, seed=0):
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    (backend, on_accel, model_cfg, engine_cfg, prompts, arrivals, max_new,
     num_requests, rate_rps) = _workload(seed, num_requests, rate_rps)
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    if on_accel:
        model.bfloat16()
    model.eval()
    engines = [ServingEngine(model, **engine_cfg) for _ in range(replicas)]
    fe = ServingFrontend(engines)
    rids, wall_s = _drive(fe, fe.step, prompts, arrivals, max_new,
                          warm_n=replicas)
    return _report(
        "serving_frontend_openloop_tokens_per_sec", fe, rids, wall_s,
        {"backend": backend, "batch": engine_cfg["max_batch_size"],
         "block_size": engine_cfg["block_size"], "replicas": replicas,
         "num_requests": num_requests, "rate_rps": rate_rps,
         "max_new_tokens": max_new})


def run_bench_fleet(num_requests=None, rate_rps=None, workers=2, seed=0):
    """Remote mode: the identical open-loop workload through a
    ServingFleet of ``workers`` spawned serving_worker.py processes,
    pinned to the CPU like this process. Refused on a chip host."""
    from paddle_tpu.inference import ServingFleet

    _refuse_workers_on_chip("--workers")
    (backend, _, model_cfg, engine_cfg, prompts, arrivals, max_new,
     num_requests, rate_rps) = _workload(seed, num_requests, rate_rps)
    spec = {"seed": 0, "model": model_cfg, "engine": engine_cfg}
    with ServingFleet(spec, num_workers=workers) as fleet:
        fe = fleet.frontend
        rids, wall_s = _drive(fe, fleet.step, prompts, arrivals, max_new,
                              warm_n=workers,
                              after_warm=fleet.reset_worker_metrics)
        merged = fleet.merged_snapshot()
        return _report(
            "serving_fleet_openloop_tokens_per_sec", fe, rids, wall_s,
            {"backend": backend, "batch": engine_cfg["max_batch_size"],
             "block_size": engine_cfg["block_size"], "workers": workers,
             "num_requests": num_requests, "rate_rps": rate_rps,
             "max_new_tokens": max_new,
             "worker_engine_steps":
                 merged["counters"].get("engine_steps_total", 0),
             "transport": "distributed/rpc HTTP, per-step round trips"})


def run_bench_prefix(num_requests=None, shared_prefix_len=None, seed=0):
    """Prefix-cache workload (ISSUE 5): requests sharing an S-token
    system prompt, served cache-off then cache-on through the frontend.
    The reported ``value`` is prefill_tokens_computed(on) / (off) — a
    deterministic counter ratio (lower is better), immune to the CPU
    container's wall-clock noise; hit rate and per-mode TTFT ride in
    ``extra``.  Asserts greedy outputs are token-identical across modes."""
    import jax
    import numpy as np

    import bench_ladder  # repo root is on sys.path (top of this file)
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    on_accel = on_tpu()
    if on_accel:
        model_cfg = dict(vocab_size=32000, hidden_size=2560,
                         intermediate_size=8192, num_hidden_layers=9,
                         num_attention_heads=10,
                         max_position_embeddings=2048, dtype="bfloat16")
        engine_cfg = dict(max_batch_size=8, max_seq_len=448, block_size=64,
                          token_budget=128, num_blocks=56)
        shared_prefix_len = shared_prefix_len or 192   # 3 full blocks
        tail_lens, max_new = (17, 33, 49), 16
        num_requests = num_requests or 16
    else:
        model_cfg = dict(vocab_size=512, hidden_size=128,
                         intermediate_size=352, num_hidden_layers=2,
                         num_attention_heads=4, max_position_embeddings=256)
        engine_cfg = dict(max_batch_size=4, max_seq_len=64, block_size=8,
                          token_budget=16, num_blocks=24)
        shared_prefix_len = shared_prefix_len or 16    # 2 full blocks
        tail_lens, max_new = (3, 5, 7), 8
        num_requests = num_requests or 8
    bs = engine_cfg["block_size"]
    if shared_prefix_len < 2 * bs:
        raise ValueError(f"--shared-prefix-len must cover >= 2 full blocks "
                         f"({2 * bs} tokens at block_size={bs})")
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, model_cfg["vocab_size"],
                         (shared_prefix_len,)).tolist()
    prompts = [prefix + rng.randint(0, model_cfg["vocab_size"],
                                    (int(rng.choice(tail_lens)),)).tolist()
               for _ in range(num_requests)]

    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    if on_accel:
        model.bfloat16()
    model.eval()

    def serve(prefix_cache):
        eng = ServingEngine(model, prefix_cache=prefix_cache, **engine_cfg)
        fe = ServingFrontend(eng)
        # the first request alone: it pays the full prefill and publishes
        # the shared blocks on retirement, so every later request can hit
        r0 = fe.submit(prompts[0], max_new_tokens=max_new)
        fe.run()
        t0 = time.monotonic()
        rids = [r0] + [fe.submit(p, max_new_tokens=max_new)
                       for p in prompts[1:]]
        fe.run()
        wall = time.monotonic() - t0
        res = fe.results()
        snap = fe.metrics.snapshot()
        return {
            "tokens": [res[r].tokens for r in rids],
            "prefill_tokens_computed": eng.prefill_tokens_computed,
            "hit_rate": snap["gauges"]["prefix_cache_hit_rate"],
            "hit_blocks": snap["counters"]["prefix_hit_blocks_total"],
            "evictions": snap["counters"]["prefix_evictions_total"],
            "p50_ttft_ms": round(
                snap["latency"]["ttft_seconds"]["p50"] * 1e3, 2),
            "wall_s": round(wall, 3),
        }

    off = serve(False)
    on = serve("auto")
    assert on["tokens"] == off["tokens"], \
        "prefix cache changed greedy outputs — parity violation"
    frac = on["prefill_tokens_computed"] / max(off["prefill_tokens_computed"],
                                               1)
    # the shared-full-block fraction of the cacheable workload (requests
    # 2..N can skip the shared blocks; request 1 must compute everything)
    sharable = (num_requests - 1) * (shared_prefix_len // bs) * bs
    total_prefill = sum(len(p) for p in prompts)
    return {
        "metric": "serving_prefix_cache_prefill_fraction",
        "value": round(frac, 4),
        "unit": "computed/uncached (lower=better)",
        "extra": {
            "host": bench_ladder.host_fingerprint(),
            "backend": backend,
            "shared_prefix_len": shared_prefix_len,
            "block_size": bs,
            "num_requests": num_requests,
            "max_new_tokens": max_new,
            "prefill_tokens_computed_off": off["prefill_tokens_computed"],
            "prefill_tokens_computed_on": on["prefill_tokens_computed"],
            "shared_fraction_bound": round(1.0 - sharable / total_prefill, 4),
            "hit_rate": round(on["hit_rate"], 4),
            "hit_blocks": on["hit_blocks"],
            "evictions": on["evictions"],
            "p50_ttft_ms_off": off["p50_ttft_ms"],
            "p50_ttft_ms_on": on["p50_ttft_ms"],
            "wall_s_off": off["wall_s"],
            "wall_s_on": on["wall_s"],
            "outputs_token_identical": True,
            "method": "same request stream served cache-off then cache-on; "
                      "value = ratio of engine prefill_tokens_computed "
                      "counters (deterministic, wall-clock-free)",
        },
    }


def run_bench_disagg(num_groups=None, group_size=None, seed=0):
    """Disaggregated prefill/decode workload (ISSUE 17): G distinct
    full-block prompts, each submitted C times CONCURRENTLY (a popular
    prompt hitting the whole fleet at once), served by two plain decode
    replicas (off) vs a prefill-role replica + the same two decode
    replicas over a KV fabric (on).  The gated ``value`` is the ratio of
    fleet-wide ``prefill_tokens_computed`` with disagg on / off —
    transferred blocks count as NOT computed (the import path writes KV
    without running attention), so the ratio falls exactly when the
    prefill-in-progress table dedupes the concurrent twins down to one
    pass and the directory moves the result instead of recomputing it
    per replica.  Deterministic counters, wall-clock-free; asserts
    greedy outputs token-identical across modes."""
    import jax
    import numpy as np

    import bench_ladder  # repo root is on sys.path (top of this file)
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend
    from paddle_tpu.inference.kv_fabric import KVFabric, MemoryKV
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    on_accel = on_tpu()
    if on_accel:
        model_cfg = dict(vocab_size=32000, hidden_size=2560,
                         intermediate_size=8192, num_hidden_layers=9,
                         num_attention_heads=10,
                         max_position_embeddings=2048, dtype="bfloat16")
        engine_cfg = dict(max_batch_size=8, max_seq_len=448, block_size=64,
                          token_budget=128, num_blocks=56)
        prompt_blocks, max_new = 3, 16
        num_groups = num_groups or 3
        group_size = group_size or 6
    else:
        model_cfg = dict(vocab_size=512, hidden_size=128,
                         intermediate_size=352, num_hidden_layers=2,
                         num_attention_heads=4, max_position_embeddings=256)
        engine_cfg = dict(max_batch_size=4, max_seq_len=64, block_size=8,
                          token_budget=16, num_blocks=24)
        prompt_blocks, max_new = 3, 8
        num_groups = num_groups or 3
        group_size = group_size or 4
    bs = engine_cfg["block_size"]
    rng = np.random.RandomState(seed)
    groups = [rng.randint(0, model_cfg["vocab_size"],
                          (prompt_blocks * bs,)).tolist()
              for _ in range(num_groups)]
    # interleaved so every dispatch round sees twins from several groups
    prompts = [groups[g] for _ in range(group_size)
               for g in range(num_groups)]

    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    if on_accel:
        model.bfloat16()
    model.eval()

    def serve(disagg):
        engines = [ServingEngine(model, **engine_cfg) for _ in range(2)]
        fab = None
        if disagg:
            for e in engines:
                e.role = "decode"
            pre = ServingEngine(model, **engine_cfg)
            pre.role = "prefill"
            engines = [pre] + engines
            fab = KVFabric(MemoryKV())
        fe = ServingFrontend(engines, kv_fabric=fab)
        t0 = time.monotonic()
        rids = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
        fe.run()
        wall = time.monotonic() - t0
        res = fe.results()
        snap = fe.metrics.snapshot()
        return {
            "tokens": [res[r].tokens for r in rids],
            "computed": sum(int(e.prefill_tokens_computed)
                            for e in engines),
            "decode_computed": sum(
                int(e.prefill_tokens_computed) for e in engines
                if getattr(e, "role", None) != "prefill"),
            "prefill_passes": snap["counters"].get(
                "fabric_prefill_passes_total", 0),
            "dedup_waits": snap["counters"].get(
                "fabric_dedup_waits_total", 0),
            "fabric": dict(fab.counters) if fab is not None else None,
            "wall_s": round(wall, 3),
        }

    off = serve(False)
    on = serve(True)
    assert on["tokens"] == off["tokens"], \
        "disaggregation changed greedy outputs — parity violation"
    frac = on["computed"] / max(off["computed"], 1)
    total_prefill = sum(len(p) for p in prompts)
    return {
        "metric": "serving_disagg_prefill_fraction",
        "value": round(frac, 4),
        "unit": "computed disagg/colocated (lower=better)",
        "extra": {
            "host": bench_ladder.host_fingerprint(),
            "backend": backend,
            "num_groups": num_groups,
            "group_size": group_size,
            "prompt_blocks": prompt_blocks,
            "block_size": bs,
            "max_new_tokens": max_new,
            "total_prompt_tokens": total_prefill,
            "prefill_tokens_computed_off": off["computed"],
            "prefill_tokens_computed_on": on["computed"],
            "decode_side_computed_on": on["decode_computed"],
            "prefill_passes": on["prefill_passes"],
            "dedup_waits": on["dedup_waits"],
            "blocks_transferred": on["fabric"]["pulled_blocks_total"],
            "bytes_transferred": on["fabric"]["pulled_bytes_total"],
            "wall_s_off": off["wall_s"],
            "wall_s_on": on["wall_s"],
            "outputs_token_identical": True,
            "method": "same concurrent identical-prompt stream served by "
                      "2 decode replicas (off) vs prefill+2 decode over "
                      "the KV fabric (on); value = ratio of fleet-summed "
                      "engine prefill_tokens_computed counters — "
                      "transferred blocks are written, not computed "
                      "(deterministic, wall-clock-free)",
        },
    }


def run_bench_disagg_wire(num_groups=None, group_size=None, seed=0,
                          transport="wire"):
    """Transport A/B for the disaggregated workload (ISSUE 20): the SAME
    prefill+2-decode fabric stream served over the frontend relay (dict
    export/import, every payload byte crosses the frontend twice) vs the
    binary data plane (a blockwire listener on the prefill replica, the
    decode replica pulls the packed buffer directly — one hop).  The
    gated ``value`` is payload hop-bytes per pulled byte:

        (wire_bytes * 1 + relay_bytes * 2) / pulled_bytes

    exactly 1.0 when every block rides the wire, exactly 2.0 when
    everything relays — a deterministic byte-counter ratio, no wall
    clock.  In-bench asserts: greedy outputs token-identical across
    transports, the decode-side imported blocks BYTE-identical across
    transports (packed re-export compared raw), and on the direct path
    the frontend relayed ZERO payload bytes (the counter the second
    rung records).  Returns BOTH rungs:
    ``serving_disagg_payload_hop_bytes`` (measured on ``transport``)
    and ``serving_disagg_frontend_relay_bytes`` (always the direct
    path's relayed bytes — 0)."""
    import jax
    import numpy as np

    import bench_ladder  # repo root is on sys.path (top of this file)
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend
    from paddle_tpu.inference.blockwire import BlockWireServer
    from paddle_tpu.inference.kv_fabric import KVFabric, MemoryKV
    from paddle_tpu.inference.serving import prompt_block_hashes
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    on_accel = on_tpu()
    if on_accel:
        model_cfg = dict(vocab_size=32000, hidden_size=2560,
                         intermediate_size=8192, num_hidden_layers=9,
                         num_attention_heads=10,
                         max_position_embeddings=2048, dtype="bfloat16")
        engine_cfg = dict(max_batch_size=8, max_seq_len=448, block_size=64,
                          token_budget=128, num_blocks=56)
        prompt_blocks, max_new = 3, 16
        num_groups = num_groups or 3
        group_size = group_size or 6
    else:
        model_cfg = dict(vocab_size=512, hidden_size=128,
                         intermediate_size=352, num_hidden_layers=2,
                         num_attention_heads=4, max_position_embeddings=256)
        engine_cfg = dict(max_batch_size=4, max_seq_len=64, block_size=8,
                          token_budget=16, num_blocks=24)
        prompt_blocks, max_new = 3, 8
        num_groups = num_groups or 3
        group_size = group_size or 4
    bs = engine_cfg["block_size"]
    rng = np.random.RandomState(seed)
    groups = [rng.randint(0, model_cfg["vocab_size"],
                          (prompt_blocks * bs,)).tolist()
              for _ in range(num_groups)]
    prompts = [groups[g] for _ in range(group_size)
               for g in range(num_groups)]
    chains = [prompt_block_hashes(g, bs) for g in groups]

    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    if on_accel:
        model.bfloat16()
    model.eval()

    def serve(wire):
        pre = ServingEngine(model, **engine_cfg)
        pre.role = "prefill"
        decs = [ServingEngine(model, **engine_cfg) for _ in range(2)]
        for e in decs:
            e.role = "decode"
        fab = KVFabric(MemoryKV())
        srv = BlockWireServer(pre) if wire else None
        try:
            fe = ServingFrontend([pre] + decs, kv_fabric=fab)
            t0 = time.monotonic()
            rids = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
            fe.run()
            wall = time.monotonic() - t0
        finally:
            if srv is not None:
                srv.close()
        res = fe.results()
        c = fab.counters
        # decode-side imported payloads, packed re-export: the raw bytes
        # the transports must agree on bit-for-bit
        payloads = {}
        for gi, hs in enumerate(chains):
            for e in decs:
                header, raw = e.export_blocks_packed(hs)
                if header["hashes"] == hs:
                    payloads[gi] = raw
                    break
        assert len(payloads) == len(chains), (
            "a prompt group's chain never landed whole on a decode "
            "replica — the transfer machinery idled")
        hop = (c["wire_bytes_total"] + 2 * c["relay_bytes_total"]) \
            / max(c["pulled_bytes_total"], 1)
        snap = fe.metrics.snapshot()["counters"]
        return {
            "tokens": [res[r].tokens for r in rids],
            "payloads": payloads,
            "hop_bytes": round(hop, 4),
            "fabric": dict(c),
            "wire_pulls_metric": snap.get("fabric_wire_pulls_total", 0),
            "relay_pulls_metric": snap.get("fabric_relay_pulls_total", 0),
            "wall_s": round(wall, 3),
        }

    relay = serve(wire=False)
    direct = serve(wire=True)
    assert direct["tokens"] == relay["tokens"], \
        "transport changed greedy outputs — parity violation"
    for gi in range(len(chains)):
        assert direct["payloads"][gi] == relay["payloads"][gi], (
            f"group {gi}: wire-imported blocks differ byte-wise from "
            "relay-imported blocks")
    # the headline contract, counter-asserted: zero payload bytes
    # through the frontend on the direct path, everything one-hop
    assert direct["fabric"]["relay_bytes_total"] == 0
    assert direct["fabric"]["relay_pulls_total"] == 0
    assert direct["fabric"]["wire_pulls_total"] >= 1
    assert direct["relay_pulls_metric"] == 0
    assert direct["wire_pulls_metric"] >= 1
    assert direct["fabric"]["wire_bytes_total"] == \
        direct["fabric"]["pulled_bytes_total"] > 0
    # and the relay leg really pays double: every byte crosses twice
    assert relay["fabric"]["wire_pulls_total"] == 0
    assert relay["hop_bytes"] >= 2.0
    assert direct["hop_bytes"] == 1.0
    run = direct if transport == "wire" else relay
    extra = {
        "host": bench_ladder.host_fingerprint(),
        "backend": backend,
        "transport": transport,
        "num_groups": num_groups,
        "group_size": group_size,
        "prompt_blocks": prompt_blocks,
        "block_size": bs,
        "max_new_tokens": max_new,
        "hop_bytes_wire": direct["hop_bytes"],
        "hop_bytes_relay": relay["hop_bytes"],
        "wire_bytes": direct["fabric"]["wire_bytes_total"],
        "relay_bytes": relay["fabric"]["relay_bytes_total"],
        "pulled_bytes_wire": direct["fabric"]["pulled_bytes_total"],
        "pulled_bytes_relay": relay["fabric"]["pulled_bytes_total"],
        "wire_pulls": direct["fabric"]["wire_pulls_total"],
        "relay_pulls": relay["fabric"]["relay_pulls_total"],
        "wall_s_wire": direct["wall_s"],
        "wall_s_relay": relay["wall_s"],
        "outputs_token_identical": True,
        "imported_blocks_byte_identical": True,
        "method": "same concurrent identical-prompt fabric stream served "
                  "relay-only vs with a blockwire listener on the prefill "
                  "replica; value = (wire_bytes*1 + relay_bytes*2) / "
                  "pulled_bytes — payload-crossing hops per transferred "
                  "byte (deterministic byte counters, wall-clock-free)",
    }
    return [
        {
            "metric": "serving_disagg_payload_hop_bytes",
            "value": run["hop_bytes"],
            "unit": "payload hops per pulled byte (1.0=direct, 2.0=relay)",
            "extra": extra,
        },
        {
            "metric": "serving_disagg_frontend_relay_bytes",
            "value": float(direct["fabric"]["relay_bytes_total"]),
            "unit": "payload bytes relayed through the frontend on the "
                    "direct path (must be 0)",
            "extra": {
                "host": bench_ladder.host_fingerprint(),
                "backend": backend,
                "wire_bytes": direct["fabric"]["wire_bytes_total"],
                "pulled_bytes": direct["fabric"]["pulled_bytes_total"],
                "method": "fabric relay_bytes_total after the direct-wire "
                          "leg of the transport A/B — asserted 0 in-bench "
                          "(every payload byte rode the data plane)",
            },
        },
    ]


def run_bench_megastep(num_requests=None, megastep_k=8, seed=0):
    """Megastep rung (ISSUE 9): a closed batch of requests served to
    completion with in-graph K-step decode vs per-token stepping.  The
    gated ``value`` is host round trips per generated token with the
    megastep ON (engine_steps_total / tokens_emitted_total — deterministic
    scheduling counters, no wall clock; lower is better, bounded below by
    the prefill steps plus 1/K).  Token parity megastep-on vs -off is
    asserted inside the bench, and per-mode tokens/s + ITL ride in
    ``extra`` for the wall-clock story."""
    import jax

    import bench_ladder  # repo root is on sys.path (top of this file)
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    on_accel = on_tpu()
    if on_accel:
        model_cfg = dict(vocab_size=32000, hidden_size=2560,
                         intermediate_size=8192, num_hidden_layers=9,
                         num_attention_heads=10,
                         max_position_embeddings=2048, dtype="bfloat16")
        engine_cfg = dict(max_batch_size=8, max_seq_len=448, block_size=64,
                          token_budget=64, num_blocks=56)
        prompt_lens, max_new = (96, 160), 32
        num_requests = num_requests or 16
    else:
        model_cfg = dict(vocab_size=512, hidden_size=128,
                         intermediate_size=352, num_hidden_layers=2,
                         num_attention_heads=4, max_position_embeddings=256)
        engine_cfg = dict(max_batch_size=4, max_seq_len=64, block_size=8,
                          token_budget=16, num_blocks=16)
        prompt_lens, max_new = (4, 8, 12), 16
        num_requests = num_requests or 12
    import numpy as np

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model_cfg["vocab_size"],
                           (int(rng.choice(prompt_lens)),)).tolist()
               for _ in range(num_requests)]
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    if on_accel:
        model.bfloat16()
    model.eval()

    def serve(k):
        eng = ServingEngine(model, megastep_k=k, **engine_cfg)
        fe = ServingFrontend(eng)
        # closed batch, submitted up front: the step/token counters are a
        # pure function of the schedule — deterministic, wall-clock-free
        warm = fe.submit(prompts[0], max_new_tokens=max_new)
        fe.run()
        assert fe.result(warm).ok
        fe.metrics.reset()
        t0 = time.monotonic()
        rids = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
        fe.run()
        wall = time.monotonic() - t0
        res = fe.results()
        snap = fe.metrics.snapshot()
        itl = snap["latency"]["token_latency_seconds"]
        return {
            "tokens": [res[r].tokens for r in rids],
            "steps": snap["counters"]["engine_steps_total"],
            "emitted": snap["counters"]["tokens_emitted_total"],
            "megasteps": snap["counters"]["megasteps_total"],
            "tokens_per_sec": round(snap["tokens_per_sec"], 1),
            "p50_itl_ms": round(itl["p50"] * 1e3, 2),
            "p95_itl_ms": round(itl["p95"] * 1e3, 2),
            "wall_s": round(wall, 3),
        }

    off = serve(1)
    on = serve(megastep_k)
    assert on["tokens"] == off["tokens"], \
        "megastep decode changed greedy outputs — parity violation"
    value = on["steps"] / max(on["emitted"], 1)
    return {
        "metric": "serving_megastep_steps_per_token",
        "value": round(value, 4),
        "unit": "host round trips/token (lower=better)",
        "extra": {
            "host": bench_ladder.host_fingerprint(),
            "backend": backend,
            "megastep_k": megastep_k,
            "num_requests": num_requests,
            "max_new_tokens": max_new,
            "steps_on": on["steps"], "steps_off": off["steps"],
            "steps_per_token_off": round(off["steps"]
                                         / max(off["emitted"], 1), 4),
            "megasteps": on["megasteps"],
            "tokens_per_sec_on": on["tokens_per_sec"],
            "tokens_per_sec_off": off["tokens_per_sec"],
            "p50_itl_ms_on": on["p50_itl_ms"],
            "p50_itl_ms_off": off["p50_itl_ms"],
            "wall_s_on": on["wall_s"], "wall_s_off": off["wall_s"],
            "outputs_token_identical": True,
            "method": "closed batch served megastep-on vs -off; value = "
                      "engine steps per emitted token with megastep on "
                      "(deterministic counters, wall-clock-free)",
        },
    }


def run_bench_staggered(num_requests=None, megastep_k=8, mean_gap=None,
                        seed=0):
    """Saturated open-loop rung (ISSUE 16): Poisson STAGGERED admission —
    requests arrive mid-flight, so under the r11 arming rule (megastep
    only once every row is past prefill) some row was always prefilling
    and the engine degraded toward per-token stepping.  The mixed-phase
    megastep packs one prompt chunk per prefilling row alongside the
    decode rows inside the scan, so it stays armed.

    Determinism: arrivals are drawn in ENGINE-STEP time (seeded
    exponential inter-arrival gaps, floored to step indices), and a
    request is admitted when the step counter passes its arrival step —
    no wall clock anywhere in the admission path or the metric.  The
    gated ``value`` is host round trips (``eng.step()`` calls) per
    emitted token with the megastep on; idle gaps with nothing scheduled
    fast-forward the virtual clock instead of counting as steps.  Token
    parity megastep-on vs -off is asserted for BOTH greedy and seeded
    sampling, and the on-mode run must actually arm mixed launches
    (``megastep_mixed`` > 0) — a rung that silently degraded to
    per-token stepping fails instead of recording."""
    import jax

    import bench_ladder  # repo root is on sys.path (top of this file)
    import numpy as np
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    on_accel = on_tpu()
    if on_accel:
        model_cfg = dict(vocab_size=32000, hidden_size=2560,
                         intermediate_size=8192, num_hidden_layers=9,
                         num_attention_heads=10,
                         max_position_embeddings=2048, dtype="bfloat16")
        engine_cfg = dict(max_batch_size=8, max_seq_len=448, block_size=64,
                          token_budget=64, num_blocks=56)
        prompt_lens, max_new = (96, 160), 32
        num_requests = num_requests or 16
        mean_gap = mean_gap if mean_gap is not None else 3.0
    else:
        model_cfg = dict(vocab_size=512, hidden_size=128,
                         intermediate_size=352, num_hidden_layers=2,
                         num_attention_heads=4, max_position_embeddings=256)
        engine_cfg = dict(max_batch_size=4, max_seq_len=64, block_size=8,
                          token_budget=16, num_blocks=16)
        prompt_lens, max_new = (4, 8, 12), 16
        num_requests = num_requests or 12
        # ~1 arrival per engine step vs a 4-row batch serving 16 tokens
        # each: offered load ~4x the service rate, so a queue forms and
        # some row is prefilling for most of the run (the saturated
        # shape where the r11 arming rule degraded to per-token steps)
        mean_gap = mean_gap if mean_gap is not None else 1.0

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model_cfg["vocab_size"],
                           (int(rng.choice(prompt_lens)),)).tolist()
               for _ in range(num_requests)]
    # open-loop Poisson arrivals in engine-step time: the offered load is
    # a fixed function of the seed, independent of service progress
    arrivals = np.floor(np.cumsum(
        rng.exponential(mean_gap, size=num_requests))).astype(int).tolist()
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    if on_accel:
        model.bfloat16()
    model.eval()

    def serve(k, sampling=None):
        eng = ServingEngine(model, megastep_k=k, **engine_cfg)
        # warm one closed request through the same engine (compile), then
        # measure from clean counters — the metric itself is step-count
        # based and unaffected, only the wall_s story benefits
        eng.add_request(prompts[0], max_new_tokens=max_new,
                        sampling=sampling)
        guard = 0
        while guard < 10_000:
            st = eng.state_summary()
            if st["num_active"] == 0 and st["queue_depth"] == 0:
                break
            eng.step()
            guard += 1
        eng.pop_finished()
        base = dict(eng.state_summary()["megastep"])
        out, steps, nxt, emitted_n = {}, 0, 0, 0
        t0 = time.monotonic()
        while True:
            while nxt < num_requests and arrivals[nxt] <= steps:
                rid = eng.add_request(prompts[nxt], max_new_tokens=max_new,
                                      sampling=sampling)
                out[rid] = []
                nxt += 1
            st = eng.state_summary()
            if st["num_active"] == 0 and st["queue_depth"] == 0:
                if nxt >= num_requests:
                    break
                # idle gap: fast-forward the virtual clock to the next
                # arrival instead of spinning no-op host round trips
                steps = max(steps, arrivals[nxt])
                continue
            got = eng.step()
            steps += 1
            for rid, toks in got.items():
                out[rid].extend(toks)
                emitted_n += len(toks)
        wall = time.monotonic() - t0
        eng.pop_finished()
        ms = eng.state_summary()["megastep"]
        return {
            "tokens": out, "steps": steps, "emitted": emitted_n,
            "megasteps": ms["megasteps"] - base["megasteps"],
            "mixed": ms.get("mixed", 0) - base.get("mixed", 0),
            "prefill_chunks": (ms.get("prefill_chunks", 0)
                               - base.get("prefill_chunks", 0)),
            "wall_s": round(wall, 3),
        }

    off = serve(1)
    on = serve(megastep_k)
    assert on["tokens"] == off["tokens"], \
        "mixed-phase megastep changed greedy outputs — parity violation"
    seeded = dict(temperature=0.8, top_k=40, top_p=0.95, seed=7)
    s_off = serve(1, sampling=seeded)
    s_on = serve(megastep_k, sampling=seeded)
    assert s_on["tokens"] == s_off["tokens"], \
        "mixed-phase megastep changed SEEDED outputs — parity violation"
    assert on["mixed"] > 0, \
        "megastep never armed a mixed launch under staggered admission " \
        "— the rung is measuring per-token stepping"
    value = on["steps"] / max(on["emitted"], 1)
    return {
        "metric": "serving_megastep_saturated_steps_per_token",
        "value": round(value, 4),
        "unit": "host round trips/token (lower=better)",
        "extra": {
            "host": bench_ladder.host_fingerprint(),
            "backend": backend,
            "megastep_k": megastep_k,
            "num_requests": num_requests,
            "max_new_tokens": max_new,
            "mean_arrival_gap_steps": mean_gap,
            "steps_on": on["steps"], "steps_off": off["steps"],
            "steps_per_token_off": round(off["steps"]
                                         / max(off["emitted"], 1), 4),
            "megasteps": on["megasteps"],
            "megasteps_mixed": on["mixed"],
            "prefill_chunks": on["prefill_chunks"],
            "wall_s_on": on["wall_s"], "wall_s_off": off["wall_s"],
            "outputs_token_identical": True,
            "seeded_outputs_token_identical": True,
            "method": "open-loop Poisson staggered admission in virtual "
                      "engine-step time; value = eng.step() host round "
                      "trips per emitted token with megastep on "
                      "(deterministic counters, wall-clock-free)",
        },
    }


def run_bench_spec(num_requests=None, spec_k=8, seed=0):
    """Speculative-decoding rung (ISSUE 19): a closed batch of REPETITIVE
    prompts (the tiny greedy model falls into token cycles — the n-gram
    drafter's showcase) served spec-on vs spec-off.  The gated ``value``
    is verify forwards per spec-committed token,
    ``spec_verify_forwards_total / (accepted_tokens_total +
    spec_verify_forwards_total)`` — each verify launch scores one
    forward-equivalent PER ROW and commits ``accepted + 1`` tokens, so
    the ratio is exactly 1.0 when nothing accepts and < 1.0 iff
    speculation pays.  Deterministic scheduling counters, no wall clock
    (ROADMAP carried note (a)).  Token parity spec-on vs spec-off is
    asserted in-bench for greedy AND seeded streams."""
    import jax

    import bench_ladder  # repo root is on sys.path (top of this file)
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    on_accel = on_tpu()
    if on_accel:
        model_cfg = dict(vocab_size=32000, hidden_size=2560,
                         intermediate_size=8192, num_hidden_layers=9,
                         num_attention_heads=10,
                         max_position_embeddings=2048, dtype="bfloat16")
        engine_cfg = dict(max_batch_size=8, max_seq_len=448, block_size=64,
                          token_budget=64, num_blocks=56)
        max_new = 64
        num_requests = num_requests or 16
    else:
        model_cfg = dict(vocab_size=512, hidden_size=128,
                         intermediate_size=352, num_hidden_layers=2,
                         num_attention_heads=4, max_position_embeddings=256)
        engine_cfg = dict(max_batch_size=4, max_seq_len=128, block_size=8,
                          token_budget=32, num_blocks=64)
        max_new = 48
        num_requests = num_requests or 8
    # repetitive workload: short cyclic patterns repeated to a fixed
    # prompt — deterministic (seeded pattern choice only), and long
    # generations so the greedy stream has room to fall into cycles
    import numpy as np

    rng = np.random.RandomState(seed)
    patterns = [[1, 2, 3], [10, 20, 30], [100, 200], [5, 6, 7]]
    prompts = []
    for i in range(num_requests):
        pat = patterns[int(rng.randint(len(patterns)))]
        rep = (pat * 8)[:8]
        prompts.append(rep)
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    if on_accel:
        model.bfloat16()
    model.eval()

    def serve(k, sampling=None):
        eng = ServingEngine(model, megastep_k=4, spec_k=k, **engine_cfg)
        fe = ServingFrontend(eng)
        warm = fe.submit(prompts[0], max_new_tokens=max_new,
                         **(sampling or {}))
        fe.run()
        assert fe.result(warm).ok
        fe.metrics.reset()
        t0 = time.monotonic()
        rids = [fe.submit(p, max_new_tokens=max_new, **(sampling or {}))
                for p in prompts]
        fe.run()
        wall = time.monotonic() - t0
        res = fe.results()
        snap = fe.metrics.snapshot()
        c = snap["counters"]
        return {
            "tokens": [res[r].tokens for r in rids],
            "emitted": c["tokens_emitted_total"],
            "verify_forwards": c.get("spec_verify_forwards_total", 0),
            "accepted": c.get("accepted_tokens_total", 0),
            "drafted": c.get("spec_draft_tokens_total", 0),
            "tokens_per_sec": round(snap["tokens_per_sec"], 1),
            "wall_s": round(wall, 3),
        }

    off = serve(0)
    on = serve(spec_k)
    assert on["tokens"] == off["tokens"], \
        "speculative decoding changed greedy outputs — parity violation"
    seeded = dict(temperature=0.8, top_k=40, top_p=0.95, seed=7)
    s_off = serve(0, sampling=seeded)
    s_on = serve(spec_k, sampling=seeded)
    assert s_on["tokens"] == s_off["tokens"], \
        "speculative decoding changed SEEDED outputs — parity violation"
    assert on["verify_forwards"] > 0, "spec never armed — no verify ran"
    assert on["accepted"] > 0, \
        "nothing accepted on the repetitive workload — the rung would " \
        "read 1.0 and the drafter is dead weight"
    value = on["verify_forwards"] / max(on["accepted"]
                                        + on["verify_forwards"], 1)
    return {
        "metric": "serving_spec_forwards_per_token",
        "value": round(value, 4),
        "unit": "verify forwards/spec-committed token (lower=better)",
        "extra": {
            "host": bench_ladder.host_fingerprint(),
            "backend": backend,
            "spec_k": spec_k,
            "num_requests": num_requests,
            "max_new_tokens": max_new,
            "verify_forwards": on["verify_forwards"],
            "accepted_tokens": on["accepted"],
            "draft_tokens": on["drafted"],
            "emitted_on": on["emitted"], "emitted_off": off["emitted"],
            "tokens_per_sec_on": on["tokens_per_sec"],
            "tokens_per_sec_off": off["tokens_per_sec"],
            "wall_s_on": on["wall_s"], "wall_s_off": off["wall_s"],
            "outputs_token_identical": True,
            "seeded_outputs_token_identical": True,
            "method": "closed repetitive batch served spec-on vs "
                      "spec-off; each verify launch counts ONE forward "
                      "per scored row, value = verify forwards / "
                      "(accepted + verify forwards) = forwards per "
                      "spec-committed token (deterministic counters, "
                      "wall-clock-free)",
        },
    }


def run_bench_tenant_isolation(num_requests=None, seed=0):
    """Tenant-fairness rung (ISSUE 18): a BURSTY tenant dumps its whole
    backlog before the STEADY tenant's arrives, then both drain through
    per-tenant DRR dispatch.  ``value`` is the steady tenant's share of
    served tokens at the halfway point — 0.5 is perfect isolation, and
    plain FIFO admission (the no-registry contrast measured into
    ``extra``) hands the window to whoever burst first.  Deterministic
    counter ratio: seeded prompts, fixed decode lengths, no wall clock
    anywhere — perf_gate additionally bounds the share absolutely
    (ABS_RUNG_BOUNDS), because drift in EITHER direction is a fairness
    bug, not an improvement."""
    import jax
    import numpy as np

    import bench_ladder
    import paddle_tpu as P
    from paddle_tpu.inference import (ServingEngine, ServingFrontend,
                                      TenantRegistry, TenantSpec)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    model_cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                     num_hidden_layers=1, num_attention_heads=2,
                     max_position_embeddings=256)
    engine_cfg = dict(max_batch_size=2, max_seq_len=64, block_size=8,
                      token_budget=16)
    per_tenant = (num_requests or 16) // 2
    max_new = 6
    rng = np.random.RandomState(seed)
    mk_prompts = lambda: [rng.randint(1, model_cfg["vocab_size"],  # noqa: E731
                                      (int(rng.choice((3, 4, 5))),)).tolist()
                          for _ in range(per_tenant)]
    bursty_prompts, steady_prompts = mk_prompts(), mk_prompts()
    total_tokens = 2 * per_tenant * max_new
    half = total_tokens // 2

    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**model_cfg))
    model.eval()

    def serve(drr):
        # quantum = one request's decode cost: each DRR round credits
        # every backlogged tenant exactly one placement, so the engine
        # queues interleave at request granularity (the default 64 would
        # cover a whole burst in one round and measure nothing)
        reg = TenantRegistry([TenantSpec("steady"), TenantSpec("bursty")],
                             quantum=max_new) if drr else None
        fe = ServingFrontend([ServingEngine(model, **engine_cfg)
                              for _ in range(2)], tenants=reg)
        tenant_of = {}
        for p in bursty_prompts:            # the burst lands first...
            tenant_of[fe.submit(p, max_new_tokens=max_new,
                                **({"tenant": "bursty"} if drr else {}))] \
                = "bursty"
        for p in steady_prompts:            # ...then steady's backlog
            tenant_of[fe.submit(p, max_new_tokens=max_new,
                                **({"tenant": "steady"} if drr else {}))] \
                = "steady"
        served = {"steady": 0, "bursty": 0}
        seen = set()
        steps = 0
        while sum(served.values()) < half and steps < 10_000:
            fe.step()
            steps += 1
            for rid, r in fe.results().items():
                if rid not in seen and r.tokens is not None:
                    seen.add(rid)
                    served[tenant_of[rid]] += len(r.tokens)
        share = served["steady"] / max(sum(served.values()), 1)
        fe.run()                            # drain the rest
        if drr:
            snap = reg.snapshot()
            assert snap["steady"]["served"] + snap["bursty"]["served"] \
                == total_tokens
        return share, served, steps

    drr_share, drr_served, drr_steps = serve(drr=True)
    fifo_share, fifo_served, fifo_steps = serve(drr=False)
    return {
        "metric": "serving_tenant_isolation_served_share",
        "value": round(drr_share, 4),
        "unit": "steady share at half-served (0.5=fair)",
        "extra": {
            "host": bench_ladder.host_fingerprint(),
            "backend": backend,
            "num_requests": 2 * per_tenant,
            "max_new_tokens": max_new,
            "drr_served_at_half": drr_served,
            "fifo_share": round(fifo_share, 4),
            "fifo_served_at_half": fifo_served,
            "steps_to_half": drr_steps,
            "method": "bursty backlog submitted before steady's; share of "
                      "served tokens credited to steady when half the "
                      "total has served — deterministic counters, DRR vs "
                      "the no-registry FIFO contrast",
        },
    }


def run_bench_warm_pool(seed=0):
    """Warm-pool time-to-capacity rung (ISSUE 18): one fleet measures a
    COLD scale-up (process launch + jax import + model build + compile)
    and a WARM claim (pre-booted pool worker: marker delete + health
    probe + attach) back to back.  ``value`` = warm_s / cold_s — lower
    is better and must stay under 1.0 (perf_gate bounds it absolutely;
    a pool that does not beat a cold boot is pure overhead)."""
    import jax

    import bench_ladder
    from paddle_tpu.inference import ServingFleet

    backend = jax.default_backend()
    _refuse_workers_on_chip("--warm-pool")
    model_cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                     num_hidden_layers=1, num_attention_heads=2,
                     max_position_embeddings=256)
    engine_cfg = dict(max_batch_size=2, max_seq_len=64, block_size=8,
                      token_budget=16)
    spec = {"seed": seed, "model": model_cfg, "engine": engine_cfg}

    def attach_time(fleet, spawn):
        t0 = time.monotonic()
        spawn()
        while fleet.num_pending_spawns and time.monotonic() - t0 < 300:
            fleet.step()
            time.sleep(0.02)
        assert fleet.num_pending_spawns == 0 and not fleet.spawn_errors, \
            f"scale-up failed: {fleet.spawn_errors}"
        return time.monotonic() - t0

    with ServingFleet(spec, num_workers=1, warm_pool_size=1,
                      spawn_timeout=240.0) as fleet:
        # cold first (named spawns bypass the pool), so the warm worker
        # finishes booting in parallel with the measurement
        cold_s = attach_time(
            fleet, lambda: fleet.spawn_worker_async(name="cold1"))
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            with fleet.warm_pool._lock:
                if fleet.warm_pool._ready:
                    break
            time.sleep(0.1)
        else:
            raise AssertionError("warm worker never became ready")
        warm_s = attach_time(fleet, fleet.spawn_worker_async)
        n_replicas = len(fleet.frontend.replicas)
        attaches = fleet.frontend.metrics.counter("pool_attaches_total")
    assert n_replicas == 3 and attaches == 1
    return {
        "metric": "serving_warm_pool_attach_ratio",
        "value": round(warm_s / cold_s, 4),
        "unit": "warm/cold time-to-capacity (lower=better)",
        "extra": {
            "host": bench_ladder.host_fingerprint(),
            "backend": backend,
            "cold_spawn_s": round(cold_s, 3),
            "warm_attach_s": round(warm_s, 3),
            "method": "same fleet, back-to-back scale-ups: cold = named "
                      "spawn (full worker boot), warm = pool claim "
                      "(marker delete + probe + attach); ratio of "
                      "time-to-attached wall clocks",
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--num-requests", type=int, default=None)
    ap.add_argument("--rate-rps", type=float, default=None)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--workers", type=int, default=0,
                    help="N>0: remote mode — N serving_worker.py processes "
                         "behind the RPC stack instead of in-process "
                         "replicas")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="S>0: prefix-cache workload — every prompt opens "
                         "with the same S-token system prompt (>= 2 full "
                         "blocks); reports hit rate + prefill tokens "
                         "computed cache-on vs cache-off")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregation workload (ISSUE 17) — concurrent "
                         "identical prompts served colocated vs prefill/"
                         "decode split over the KV fabric; reports the "
                         "fleet-wide computed-prefill-token ratio "
                         "(transferred blocks count as not-computed)")
    ap.add_argument("--wire", action="store_true",
                    help="with --disagg: transport A/B (ISSUE 20) — the "
                         "fabric stream over the frontend relay vs the "
                         "binary blockwire data plane; reports payload "
                         "hop-bytes per pulled byte on the DIRECT path "
                         "(1.0) plus the frontend-relayed-bytes rung (0)")
    ap.add_argument("--relay", action="store_true",
                    help="with --disagg: the same transport A/B but the "
                         "hop-bytes rung records the RELAY leg (2.0) — "
                         "the operator-facing worst-case view")
    ap.add_argument("--megastep", action="store_true",
                    help="megastep workload — a closed batch served with "
                         "in-graph K-step decode vs per-token stepping; "
                         "reports host round trips per token + parity")
    ap.add_argument("--megastep-k", type=int, default=8)
    ap.add_argument("--tenant-isolation", action="store_true",
                    help="tenant-fairness workload (ISSUE 18) — bursty "
                         "backlog vs steady backlog through per-tenant "
                         "DRR dispatch; reports the steady tenant's "
                         "served-token share at half-served (0.5=fair), "
                         "a deterministic counter ratio")
    ap.add_argument("--warm-pool", action="store_true",
                    help="warm-pool workload (ISSUE 18) — cold worker "
                         "spawn vs warm pool claim on one fleet; reports "
                         "warm/cold time-to-capacity ratio (< 1.0 or the "
                         "pool is overhead)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding workload (ISSUE 19) — a "
                         "closed repetitive batch served spec-on vs "
                         "spec-off; reports verify forwards per "
                         "spec-committed token (< 1.0 iff the n-gram "
                         "drafter pays) + greedy/seeded parity")
    ap.add_argument("--spec-k", type=int, default=8)
    ap.add_argument("--staggered-admission", action="store_true",
                    help="saturated megastep workload — open-loop Poisson "
                         "staggered admission in virtual engine-step time; "
                         "reports host round trips per token with the "
                         "mixed-phase megastep on + greedy/seeded parity")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.spec:
        line = run_bench_spec(num_requests=args.num_requests,
                              spec_k=args.spec_k, seed=args.seed)
    elif args.tenant_isolation:
        line = run_bench_tenant_isolation(num_requests=args.num_requests,
                                          seed=args.seed)
    elif args.warm_pool:
        line = run_bench_warm_pool(seed=args.seed)
    elif args.disagg and (args.wire or args.relay):
        line = run_bench_disagg_wire(
            seed=args.seed, transport="relay" if args.relay else "wire")
    elif args.disagg:
        line = run_bench_disagg(seed=args.seed)
    elif args.staggered_admission:
        line = run_bench_staggered(num_requests=args.num_requests,
                                   megastep_k=args.megastep_k,
                                   seed=args.seed)
    elif args.megastep:
        line = run_bench_megastep(num_requests=args.num_requests,
                                  megastep_k=args.megastep_k,
                                  seed=args.seed)
    elif args.shared_prefix_len > 0:
        line = run_bench_prefix(num_requests=args.num_requests,
                                shared_prefix_len=args.shared_prefix_len,
                                seed=args.seed)
    elif args.workers > 0:
        line = run_bench_fleet(num_requests=args.num_requests,
                               rate_rps=args.rate_rps,
                               workers=args.workers, seed=args.seed)
    else:
        line = run_bench(num_requests=args.num_requests,
                         rate_rps=args.rate_rps,
                         replicas=args.replicas, seed=args.seed)
    for rung in (line if isinstance(line, list) else [line]):
        print(json.dumps(rung))


if __name__ == "__main__":
    main()
