#!/usr/bin/env python
"""Measure the compiled pipeline's ACTUAL bubble vs the synchronous bound.

Method (slope/intercept decomposition — the only sound way to separate
bubble from per-microbatch work without per-op tracing): run the SAME
P-stage compiled pipeline at several microbatch counts M and fit

    t(M) = a*M + b

a = steady-state per-microbatch time (all stages busy), b = the per-step
fixed cost: pipeline fill/drain (the bubble) + dispatch overhead. The
synchronous 1F1B bound says fill+drain idles each stage for (P-1)
microbatch-times, so b_bubble_bound = (P-1)*a. We report

    measured_bubble_ticks = b / a      (vs the P-1 bound)
    idle_fraction(M)      = b / t(M)   (vs (P-1)/(M+P-1))

For VPP (C chunks), the interleaved-1F1B promise is a bubble of (P-1)/C
chunk-times = (P-1)/C microbatch-times; chunk-sequential rings without
cross-chunk overlap pay ~C*(P-1) chunk-times = (P-1) microbatch-times
(same as non-VPP). Comparing b_vpp/a_vpp against (P-1) and (P-1)/C tells
whether XLA's scheduler recovers the interleaving benefit the
compiled_pipeline docstring hopes for.

r6 adds the 4th row: the BRANCH-FREE interleaved tick (weights gathered
from the stacked [C, P, ...] arrays with lax.dynamic_index_in_dim) vs the
lax.switch selection (PADDLE_TPU_VPP_INTERLEAVED_IMPL=switch). Note the
switch row is NOT the full r5 tick: the r6 pending-buffer removal applies
to both impls, so this A/B isolates exactly the branch-vs-gather cost;
the r5 tick additionally carried an [M, ...] scatter/gather per tick.

Runs on the virtual 8-device CPU mesh (pipeline needs >1 device; the
schedule geometry, not chip speed, is under test). The mesh is pp-only
(dp=mp=1): this jax build's SPMD partitioner cannot mix the manual 'pp'
axis with real auto axes (see compiled_pipeline._pp_collectives_native),
and schedule geometry does not depend on mp. Prints one JSON line.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-switch", action="store_true",
                    help="omit the r5 lax.switch interleaved row")
    cli = ap.parse_args()
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet.meta_parallel import (
        CompiledPipelineTrainStep,
        PipelineLayer,
    )
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.models import (
        LlamaConfig,
        LlamaPretrainingCriterion,
        llama_pipeline_descs,
    )

    PSTAGES = 4
    MS = [4, 8, 16, 32]
    REPS = 5
    # enough per-stage compute that a*M dominates dispatch noise on CPU
    cfg = LlamaConfig(vocab_size=1024, hidden_size=256, intermediate_size=704,
                      num_hidden_layers=8, num_attention_heads=8,
                      max_position_embeddings=256)
    crit = LlamaPretrainingCriterion()

    def measure(num_chunks):
        set_hybrid_communicate_group(None)
        s = dist.fleet.DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                            "pp_degree": PSTAGES, "sharding_degree": 1,
                            "sep_degree": 1}
        dist.fleet.init(is_collective=True, strategy=s)
        out = {}
        for M in MS:
            P.seed(0)
            pipe = PipelineLayer(
                layers=llama_pipeline_descs(cfg), num_stages=PSTAGES,
                loss_fn=lambda lo, la: crit(lo, la),
                seg_method="layer:_PipeDecoder",  # 2 decoders per segment
                num_virtual_pipeline_stages=(num_chunks if num_chunks > 1
                                             else None))
            opt = P.optimizer.AdamW(learning_rate=1e-4,
                                    parameters=pipe.parameters())
            step = CompiledPipelineTrainStep(pipe, opt, num_micro=M)
            ids = P.to_tensor(np.random.RandomState(0).randint(
                0, cfg.vocab_size, (2 * M, 64)).astype(np.int32))
            float(step(ids, ids).numpy())  # compile + warm
            best = 1e9
            for _ in range(REPS):
                t0 = time.perf_counter()
                loss = step(ids, ids)
                float(loss.numpy())
                best = min(best, time.perf_counter() - t0)
            out[M] = best
        # least-squares fit t = a*M + b
        xs = np.asarray(MS, float)
        ys = np.asarray([out[m] for m in MS])
        a, b = np.polyfit(xs, ys, 1)
        return out, float(a), float(b)

    t1, a1, b1 = measure(num_chunks=1)
    os.environ["PADDLE_TPU_VPP_INTERLEAVED"] = "0"
    t2, a2, b2 = measure(num_chunks=2)       # chunk-sequential rings (forced)
    del os.environ["PADDLE_TPU_VPP_INTERLEAVED"]
    t3, a3, b3 = measure(num_chunks=2)       # branch-free interleaved (auto)
    t4 = a4 = b4 = None
    if not cli.skip_switch:
        os.environ["PADDLE_TPU_VPP_INTERLEAVED_IMPL"] = "switch"
        t4, a4, b4 = measure(num_chunks=2)   # r5 lax.switch interleaved tick
        del os.environ["PADDLE_TPU_VPP_INTERLEAVED_IMPL"]

    def report(tag, t, a, b, C):
        bound = (PSTAGES - 1)  # microbatch-times of bubble, non-interleaved
        interleaved_bound = (PSTAGES - 1) / C
        return {
            "step_s_by_M": {str(m): round(v, 4) for m, v in t.items()},
            "per_micro_s": round(a, 5),
            "fixed_s": round(b, 5),
            "measured_bubble_ticks": round(b / a, 2) if a > 0 else None,
            "sync_1f1b_bound_ticks": bound,
            "interleaved_bound_ticks": round(interleaved_bound, 2),
            "idle_fraction_at_M8": round(b / (a * 8 + b), 3),
            "sync_bound_idle_at_M8": round(bound / (8 + bound), 3),
        }

    res = {
        "pp_stages": PSTAGES,
        "mesh": "cpu-8dev dp1.mp1.pp4",
        "non_vpp": report("novpp", t1, a1, b1, 1),
        "vpp_c2_chunk_sequential": report("vpp-seq", t2, a2, b2, 2),
        "vpp_c2_interleaved_indexed": report("vpp-il", t3, a3, b3, 2),
        "interleaved_bubble_vs_sequential": (round(b3 / b2, 3)
                                             if b2 > 0 else None),
        # the tentpole check (ISSUE r6): branch-free interleaved must hold
        # its bubble win WITHOUT the r5 steady-state tax — a within ~10%
        # of chunk-sequential's
        "indexed_steady_state_vs_sequential": (round(a3 / a2, 3)
                                               if a2 > 0 else None),
        "vpp_recovers_interleaving": bool(b3 / a3 < (PSTAGES - 1) * 0.75
                                          if a3 > 0 else False),
    }
    if t4 is not None:
        res["vpp_c2_interleaved_switch_r5"] = report("vpp-il-sw", t4, a4, b4, 2)
        res["switch_steady_state_vs_indexed"] = (round(a4 / a3, 3)
                                                 if a3 > 0 else None)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
