#!/usr/bin/env python
"""BASELINE ladder rungs beyond the flagship (BASELINE.md configs):
ResNet-50 ImageNet-shape training imgs/sec/chip and BERT-base-class finetune
step time. Prints one JSON line per rung. The flagship Llama rung stays in
bench.py (the driver's single-line contract).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import numpy as np

from paddle_tpu.device import on_tpu
from paddle_tpu.jit import use_compile_cache


def host_fingerprint():
    """Identity of the machine a wall-clock rung was measured on.  The
    perf gate treats 'host' as a measurement-config key: rungs recorded
    on different hosts re-baseline loudly instead of being compared —
    r7 measured the SAME seed code 1.6-2.2x apart across two 'cpu'
    dev containers, so cross-host CPU numbers are garbage to gate on."""
    import platform

    model = "unknown"
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    slug = "".join(c if c.isalnum() else "-" for c in model)[:40].strip("-")
    return f"{platform.machine()}-{os.cpu_count()}c-{slug}"


def _timeit(step, args, steps):
    """Multi-step timing: the whole window runs as ONE compiled scan
    (TrainStep.run_steps), so per-dispatch host overhead — large for models
    with hundreds of small param tensors — is paid once, as a real
    serving/training loop would."""
    import numpy as np

    stacks = [a.__class__(jnp_broadcast(a, steps)) for a in args]
    losses = step.run_steps(*stacks)  # compile + run
    losses.numpy()
    t0 = time.perf_counter()
    losses = step.run_steps(*stacks)
    ls = losses.numpy()
    return (time.perf_counter() - t0) / steps, float(ls[-1])


def jnp_broadcast(t, k):
    import jax.numpy as jnp

    v = t._value
    return jnp.broadcast_to(v, (k, *v.shape))


def bench_resnet50():
    import paddle_tpu as P
    from paddle_tpu.vision.models import resnet50

    backend = jax.default_backend()
    on_accel = on_tpu()
    P.seed(0)
    batch = 128 if on_accel else 4
    size = 224 if on_accel else 32
    steps = 10 if on_accel else 2
    model = resnet50(num_classes=1000)
    if on_accel:
        model.bfloat16()
    opt = P.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                               parameters=model.parameters(),
                               multi_precision=on_accel)
    step = P.jit.TrainStep(
        model, lambda m, x, y: P.nn.functional.cross_entropy(m(x), y), opt)
    x = P.to_tensor(np.random.RandomState(0).rand(batch, 3, size, size).astype(np.float32))
    if on_accel:
        x = x.astype("bfloat16")
    y = P.to_tensor(np.random.RandomState(1).randint(0, 1000, (batch,)).astype(np.int64))
    dt, loss = _timeit(step, (x, y), steps)
    print(json.dumps({
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": round(batch / dt, 1),
        "unit": "imgs/s",
        "extra": {"backend": backend, "host": host_fingerprint(),
                  "batch": batch, "img": size,
                  "step_ms": round(dt * 1e3, 2), "loss": loss},
    }))


def bench_bert_base():
    import paddle_tpu as P
    from paddle_tpu import nn

    backend = jax.default_backend()
    on_accel = on_tpu()
    P.seed(0)
    if on_accel:
        h, layers, heads, seq, batch, vocab, steps = 768, 12, 12, 128, 32, 30522, 10
    else:
        h, layers, heads, seq, batch, vocab, steps = 64, 2, 4, 32, 4, 512, 2

    class BertClassifier(nn.Layer):
        """BERT-base-shape encoder + pooler + 2-way head (finetune config)."""

        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(vocab, h)
            self.pos = nn.Embedding(seq, h)
            enc_layer = nn.TransformerEncoderLayer(h, heads, 4 * h, dropout=0.1,
                                                   activation="gelu")
            self.encoder = nn.TransformerEncoder(enc_layer, layers)
            self.cls = nn.Linear(h, 2)

        def forward(self, ids):
            import paddle_tpu as P

            x = self.embed(ids) + self.pos(P.arange(seq).astype("int32"))
            return self.cls(self.encoder(x)[:, 0])

    model = BertClassifier()
    if on_accel:
        model.bfloat16()
    opt = P.optimizer.AdamW(learning_rate=2e-5, parameters=model.parameters(),
                            multi_precision=on_accel)
    step = P.jit.TrainStep(
        model, lambda m, ids, y: P.nn.functional.cross_entropy(m(ids), y), opt)
    ids = P.to_tensor(np.random.RandomState(0).randint(0, vocab, (batch, seq)).astype(np.int32))
    y = P.to_tensor(np.random.RandomState(1).randint(0, 2, (batch,)).astype(np.int64))
    dt, loss = _timeit(step, (ids, y), steps)
    print(json.dumps({
        "metric": "bert_base_finetune_step_ms",
        "value": round(dt * 1e3, 2),
        "unit": "ms/step",
        "extra": {"backend": backend, "host": host_fingerprint(),
                  "batch": batch, "seq": seq,
                  "examples_per_sec": round(batch / dt, 1), "loss": loss},
    }))


def bench_llama_decode():
    """Serving decode rung: static-KV-cache autoregressive generation on the
    ~1B flagship (ideal is HBM-bound: all params stream per token)."""
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, greedy_decode

    backend = jax.default_backend()
    on_accel = on_tpu()
    P.seed(0)
    if on_accel:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2560, intermediate_size=8192,
                          num_hidden_layers=9, num_attention_heads=10,
                          max_position_embeddings=2048, dtype="bfloat16")
        batch, prompt, new = 8, 128, 64
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=352,
                          num_hidden_layers=2, num_attention_heads=4,
                          max_position_embeddings=256)
        batch, prompt, new = 2, 8, 8
    model = LlamaForCausalLM(cfg)
    if on_accel:
        model.bfloat16()
    model.eval()
    ids = P.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, prompt)).astype(np.int32))

    # whole decode loop compiled into ONE program. Per-step time comes from
    # the SLOPE between two decode lengths: a single call carries a fixed
    # dispatch+sync overhead that is not the serving step — the slope
    # isolates the per-token cost.
    ring = prompt + (3 * new if on_accel else new)

    def run(n):
        out = greedy_decode(model, ids, max_new_tokens=n, max_length=ring)
        out.numpy()  # compile + warm
        best = 1e9
        # CPU hosts: the whole call is ~4 ms, so a single timed repeat is
        # one scheduler preemption away from a 2x misread (r10 measured
        # 2.4k-4.1k tok/s across identical runs) — best-of-5 picks the
        # un-preempted call, same hardening the serving rung got in r8
        for _ in range(2 if on_accel else 5):
            t0 = time.perf_counter()
            out = greedy_decode(model, ids, max_new_tokens=n, max_length=ring)
            out.numpy()
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo = run(new)
    if on_accel:
        t_hi = run(3 * new)
        per_step = (t_hi - t_lo) / (2 * new)
    else:
        per_step = t_lo / new
    tps = batch / per_step
    print(json.dumps({
        "metric": "llama_1b_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "extra": {"backend": backend, "host": host_fingerprint(),
                  "batch": batch, "prompt": prompt,
                  "new_tokens": new, "ring": ring,
                  "ms_per_token_per_seq": round(per_step * 1e3, 2),
                  "method": "slope over decode lengths (removes the fixed "
                            "per-call dispatch overhead); "
                            "best-of-5 timed calls per point on CPU hosts",
                  "single_call_s": round(t_lo, 3)},
    }))


def bench_serving_mixed():
    """Continuous-batching serving rung: steady-state
    full-batch decode over the paged-KV cache with MIXED per-sequence
    context lengths. Device cost comes from an in-graph lax.scan of the
    engine's pure-decode step (one program, n steps) timed by the SLOPE
    between two scan lengths, which drops the fixed per-call overhead. A
    short engine.run() with staggered admissions cross-checks end-to-end
    behavior."""
    import jax.numpy as jnp
    from jax import lax

    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    backend = jax.default_backend()
    on_accel = on_tpu()
    P.seed(0)
    if on_accel:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2560,
                          intermediate_size=8192, num_hidden_layers=9,
                          num_attention_heads=10,
                          max_position_embeddings=2048, dtype="bfloat16")
        B, block, budget, max_seq = 8, 64, 64, 448
        ctx0 = [128, 192, 256, 320, 128, 192, 256, 320]  # mixed lengths
        n_lo, n_hi = 8, 24
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=352, num_hidden_layers=2,
                          num_attention_heads=4, max_position_embeddings=256)
        B, block, budget, max_seq = 4, 8, 16, 64
        ctx0 = [8, 12, 16, 20]
        n_lo, n_hi = 4, 12
    model = LlamaForCausalLM(cfg)
    if on_accel:
        model.bfloat16()
    model.eval()
    eng = ServingEngine(model, max_batch_size=B, max_seq_len=max_seq,
                        block_size=block, token_budget=budget)

    # fill the paged caches to the mixed context lengths via real prefills
    rng = np.random.RandomState(0)
    for c in ctx0:
        eng.add_request(rng.randint(0, cfg.vocab_size, (c,)).tolist(),
                        max_new_tokens=max_seq - c - 1)
    eng.step()  # admission happens inside step()
    while eng._queue or any(r.in_prefill for r in eng._active.values()):
        eng.step()

    # steady-state decode: scan the raw step body n times in ONE program.
    # Engine decode convention: the freshly sampled token is fed (and its
    # KV cached) at position context_len - 1.
    enc = jnp.zeros((B,), jnp.int32)
    now = jnp.ones((B,), jnp.int32)
    cu = jnp.arange(B + 1, dtype=jnp.int32)
    bt = jnp.asarray(eng.block_tables)
    by_slot = sorted(eng._active.values(), key=lambda r: r.slot)
    dec0 = jnp.asarray([r.context_len - 1 for r in by_slot], jnp.int32)
    toks0 = jnp.asarray([r.generated[-1] for r in by_slot], jnp.int32)

    def body(weights, carry, _):
        toks, kcs, vcs, dec = carry
        nxt, kcs, vcs, _ = eng._step_raw(
            weights, kcs, vcs, eng._rope, toks, enc, dec, now, cu,
            bt, 1)
        return (nxt, kcs, vcs, dec + 1), nxt[0]

    progs = {}  # one compile per scan length, shared across slope repeats

    def run_n(n):
        prog = progs.get(n)
        if prog is None:
            @jax.jit
            def prog(weights, kcs, vcs):
                # weights MUST be arguments: closing over the ~2 GB pytree
                # would embed it in the program as constants
                (_, kcs, vcs, _), out = lax.scan(
                    lambda c, x: body(weights, c, x),
                    (toks0, list(kcs), list(vcs), dec0), None, length=n)
                return out[-1]
            progs[n] = prog
        o = prog(eng._weights, eng.key_caches, eng.value_caches)  # compile/warm
        float(o)
        best = 1e9
        for _ in range(4):
            t0 = time.perf_counter()
            float(prog(eng._weights, eng.key_caches, eng.value_caches))
            best = min(best, time.perf_counter() - t0)
        return best

    # the slope SUBTRACTS two noisy minima, so scheduler jitter on a
    # shared-vCPU host amplifies: r8 measured 13.5k vs 24.2k tok/s on
    # identical code back-to-back with the old best-of-2 single slope.
    # Harden: best-of-4 per point, 3 full slope repeats, keep the min
    # POSITIVE per-step (the least-interference estimate) — a repeat whose
    # subtraction goes non-positive is pure interference and is discarded,
    # not clamped (a clamped 1e-9 inside the min would win and record an
    # absurd ~1e10 tok/s baseline)
    pairs = [(run_n(n_lo), run_n(n_hi)) for _ in range(3)]
    positive = [(hi - lo) / (n_hi - n_lo) for lo, hi in pairs if hi > lo]
    if positive:
        per_step, slope_fallback = min(positive), False
    else:
        # every repeat's subtraction went non-positive (pathological host
        # interference): fall back to whole-scan time over steps — it
        # folds the fixed dispatch overhead in (underestimates tok/s,
        # never records an absurd 1e10 baseline the gate would then hold
        # every honest round against)
        per_step, slope_fallback = min(hi for _, hi in pairs) / n_hi, True
    tps = B / per_step

    # end-to-end cross-check: staggered mixed-length service completes
    eng2 = ServingEngine(model, max_batch_size=B, max_seq_len=max_seq,
                         block_size=block, token_budget=budget)
    pr = [rng.randint(0, cfg.vocab_size, (c,)).tolist()
          for c in ([5, 17, 9, 13] if not on_accel else [64, 200, 96, 150])]
    t0 = time.perf_counter()
    outs = {}
    r0 = eng2.add_request(pr[0], max_new_tokens=8)
    r1 = eng2.add_request(pr[1], max_new_tokens=8)
    eng2.step()
    r2 = eng2.add_request(pr[2], max_new_tokens=8)
    r3 = eng2.add_request(pr[3], max_new_tokens=8)
    outs = eng2.run()
    e2e_s = time.perf_counter() - t0
    ok = all(len(outs[r]) == 8 for r in (r0, r1, r2, r3))

    print(json.dumps({
        "metric": "serving_mixed_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "extra": {"backend": backend, "host": host_fingerprint(),
                  "batch": B, "ctx_lengths": ctx0,
                  "block_size": block, "paged_cache": True,
                  "ms_per_step": round(per_step * 1e3, 3),
                  "slope_fallback": slope_fallback,
                  "method": "min over 3 slope repeats, in-graph scan "
                            f"lengths {n_lo} vs {n_hi} steps, best-of-4 "
                            "per point",
                  "e2e_staggered_admission_ok": ok,
                  "e2e_wallclock_s": round(e2e_s, 2)},
    }))


def _load_bench_serving():
    """tools/bench_serving.py by path (it is a script dir, not a package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_serving",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "bench_serving.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_serving_frontend():
    """Serving control-plane rung (ISSUE 2): open-loop Poisson arrivals
    through ServingFrontend (admission, priority routing, preemption under
    a deliberately tight block pool) — steady-state tokens/s plus p50/p95
    TTFT. The heavy lifting lives in tools/bench_serving.py; this rung
    re-emits its JSON line so the perf gate sees it in the ladder."""
    print(json.dumps(_load_bench_serving().run_bench()))


def bench_serving_fleet():
    """Cross-host fleet rung (ISSUE 3): the frontend rung's open-loop
    Poisson workload, but served by 2 remote serving_worker.py processes
    over the RPC stack instead of in-process replicas — measures what the
    per-step HTTP round trips and state-mirror sync cost against the
    in-process number directly above it in the ladder."""
    print(json.dumps(_load_bench_serving().run_bench_fleet(workers=2)))


def bench_serving_prefix():
    """Prefix-cache rung (ISSUE 5): a shared-system-prompt request stream
    served cache-off then cache-on; value = the ratio of prefill tokens
    actually computed (deterministic engine counters, lower is better).
    Greedy parity across modes is asserted inside the bench — a rung that
    'wins' by emitting different tokens fails instead of recording."""
    print(json.dumps(_load_bench_serving().run_bench_prefix()))


def bench_serving_disagg():
    """Disaggregation rung (ISSUE 17): concurrent identical prompts
    served colocated (2 decode replicas) vs split (prefill replica + the
    same decode replicas over the KV fabric); value = the ratio of
    fleet-wide prefill tokens actually computed (deterministic engine
    counters, lower is better — transferred blocks are written, not
    computed).  Greedy parity across modes is asserted inside the
    bench."""
    print(json.dumps(_load_bench_serving().run_bench_disagg()))


def bench_serving_megastep():
    """Megastep rung (ISSUE 9): a closed request batch served with K-step
    in-graph decode vs per-token stepping; value = host round trips per
    generated token with the megastep on (deterministic scheduling
    counters, lower is better, bound = prefill steps + 1/K).  Token
    parity megastep-on vs -off is asserted inside the bench."""
    print(json.dumps(_load_bench_serving().run_bench_megastep()))


def bench_serving_megastep_saturated():
    """Saturated megastep rung (ISSUE 16): open-loop Poisson STAGGERED
    admission in virtual engine-step time — the traffic shape where the
    r11 megastep disarmed (some row always prefilling) and the engine
    degraded toward per-token stepping.  With the mixed-phase scan the
    megastep stays armed; value = host round trips per emitted token
    with megastep on (deterministic counters).  Greedy AND seeded parity
    megastep-on vs -off are asserted inside the bench, and the run fails
    unless at least one mixed launch actually armed."""
    print(json.dumps(_load_bench_serving().run_bench_staggered()))


def bench_pipeline_compiled_vs_eager():
    """Compiled-vs-eager pipeline rung: the same dp2×mp2×pp2 llama microbatch
    schedule through the eager per-op 1F1B engine vs CompiledPipelineTrainStep
    (one XLA program). Runs on a virtual 8-device CPU mesh in a subprocess —
    pipeline parallelism needs >1 device, and the comparison (host-dispatch
    overhead vs one fused program) is the quantity of interest."""
    import subprocess

    child = os.environ.get("_PADDLE_TPU_PP_BENCH_CHILD") == "1"
    if not child:
        env = dict(os.environ)
        env["_PADDLE_TPU_PP_BENCH_CHILD"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count=8")
        env["XLA_FLAGS"] = " ".join(flags)
        subprocess.run([sys.executable, os.path.abspath(__file__), "pipeline"],
                       env=env, check=True)
        return

    import paddle_tpu as P
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet.meta_parallel import (
        CompiledPipelineTrainStep,
        PipelineLayer,
    )
    from paddle_tpu.models import (
        LlamaPretrainingCriterion,
        llama_pipeline_descs,
        llama_tiny,
    )

    P.seed(0)
    dmp = 2
    s = dist.fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": dmp, "mp_degree": dmp, "pp_degree": 2,
                        "sharding_degree": 1, "sep_degree": 1}
    s.pipeline_configs = {"accumulate_steps": 4, "schedule_mode": "1F1B"}
    dist.fleet.init(is_collective=True, strategy=s)
    cfg = llama_tiny()
    crit = LlamaPretrainingCriterion()
    pipe = PipelineLayer(layers=llama_pipeline_descs(cfg), num_stages=2,
                         loss_fn=lambda lo, la: crit(lo, la))
    model = dist.fleet.distributed_model(pipe)
    opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    ids = P.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (8, 32)).astype(np.int32))
    reps = 5
    model.train_batch([ids, ids], opt)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        loss_e = model.train_batch([ids, ids], opt)
    float(loss_e.numpy())
    eager_ms = (time.perf_counter() - t0) / reps * 1e3

    cstep = CompiledPipelineTrainStep(pipe, getattr(opt, "_inner", opt),
                                      num_micro=4)
    float(cstep(ids, ids).numpy())  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        loss_c = cstep(ids, ids)
    float(loss_c.numpy())
    comp_ms = (time.perf_counter() - t0) / reps * 1e3
    print(json.dumps({
        "metric": "pp_llama_step_ms_compiled_vs_eager",
        "value": round(comp_ms, 2),
        "unit": "ms/step",
        "extra": {"backend": "cpu-mesh-8dev", "host": host_fingerprint(),
                  "mesh": f"dp{dmp}.mp{dmp}.pp2",
                  "eager_step_ms": round(eager_ms, 2),
                  "speedup_vs_eager": round(eager_ms / comp_ms, 2),
                  "num_micro": 4},
    }))


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    use_compile_cache()
    if which in ("all", "resnet"):
        bench_resnet50()
    if which in ("all", "bert"):
        bench_bert_base()
    if which in ("all", "decode"):
        bench_llama_decode()
    if which in ("all", "serving"):
        bench_serving_mixed()
    if which in ("all", "frontend"):
        bench_serving_frontend()
    if which == "fleet" or (which == "all" and not on_tpu()):
        bench_serving_fleet()
    elif which == "all":
        print("bench_ladder: fleet rung not measured here: its worker "
              "processes would each need a chip of their own and this "
              "process holds the host's", file=sys.stderr)
    if which in ("all", "prefix"):
        bench_serving_prefix()
    if which in ("all", "disagg"):
        bench_serving_disagg()
    if which in ("all", "megastep"):
        bench_serving_megastep()
    if which in ("all", "megastep_saturated"):
        bench_serving_megastep_saturated()
    if which in ("all", "pipeline"):
        bench_pipeline_compiled_vs_eager()
