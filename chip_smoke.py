#!/usr/bin/env python
"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py              # one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4    # only the dp2 x mp2 mesh and its reference

Drives the two hot paths once, through the entry points a user calls, at the
published Llama-2-7B widths (``LlamaConfig``'s defaults: hidden 4096, 32 heads
of 128, intermediate 11008, vocab 32000) with only depth cut to fit one 16 GB
chip and weights made from a seed:

- kernels: the Pallas flash attention forward and backward, compiled, against
  the float32 jnp reference and its ``jax.vjp``;
- train: ``jit.TrainStep`` over ``LlamaForCausalLM`` (bf16, recompute,
  ``AdamW(multi_precision=True)``) through ``__call__`` and ``run_steps``;
- serve: ``ServingEngine`` behind an in-process ``ServingFrontend``, requests
  admitted staggered so the single-step, megastep, mixed-phase and
  speculative-verify programs all run, every emitted token checked against
  the model's own forward on the same tokens;
- with ``--chips 4``: ``fleet.init`` dp 2 x mp 2, ``distributed_model`` +
  ``distributed_optimizer`` + ``TrainStep`` in one process across four chips,
  against the unsharded step on one of them.

One process, no children, no network. It fails the moment JAX finds no TPU, a
phase raises or a check does not hold: exit code 1 and a last line with
``"ok": false``. On success the last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
each phase prints one JSON line before it.
"""
import argparse
import gc
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SEED = 0

# Llama-2-7B widths are LlamaConfig's defaults; these are the cuts.
TRAIN = dict(
    model=dict(num_hidden_layers=2, max_position_embeddings=2048,
               dtype="bfloat16", recompute=True),
    reduced={"num_hidden_layers": "32 -> 2: 14 B of parameter + optimizer "
             "state per parameter (bf16 + fp32 master + two fp32 moments) is "
             "9.3 GB at 667 M parameters; a third layer makes it 12.2 GB "
             "before activations in 15.75 GiB"},
    batch=2, seq=2048, steps=3, lr=1e-4,
    # two XLA programs (the step alone, the step inside a scan) over the same
    # bf16 math: losses near 10 agree to a few bf16 ulps of the logits
    entry_tol=0.05,
)
SERVE = dict(
    model=dict(num_hidden_layers=8, max_position_embeddings=4096,
               dtype="bfloat16"),
    reduced={"num_hidden_layers": "32 -> 8: 3.8 GB of bf16 weights beside a "
             "3 GiB block pool and the reference forward"},
    engine=dict(max_batch_size=4, max_seq_len=2304, block_size=64,
                token_budget=256, num_blocks=384, megastep_k=8),
    # (prompt tokens, new tokens), in order of submission
    first=(1800, 64), staggered=[(300, 48), (900, 32)], last=(520, 32),
    spec_k=4, spec_request=(480, 32), spec_period=24,
    # bf16 through 8 layers in two different programs (paged XLA attention in
    # the engine, the flash kernel in the reference): logits near 2 in bf16
    # step by 2**-6 = 0.016, and the two programs were measured one such step
    # apart on the chip; the tolerance is six. A wrong position, mask or block
    # table puts the emitted token about 2 nats (four standard deviations of a
    # random-init logit) below the reference's best.
    logit_tol=0.1,
)
MESH = dict(
    model=TRAIN["model"], reduced=TRAIN["reduced"],
    batch=4, seq=2048, steps=3, lr=1e-4,
    # sharded and unsharded steps reduce in different orders in bf16
    loss_tol=0.05,
)


class CheckFailed(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise CheckFailed(what)


def emit(obj):
    print(json.dumps(obj), flush=True)


class CompileMeter:
    """Counts what jax compiles, from jax.monitoring's own events: every
    program handed to the backend, the seconds the backend took for it (XLA
    compiling, or reading the persistent cache), and the cache's hits.
    Tracing and lowering are host work and stay in the wall clock."""

    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.programs, self.seconds, self.cache_hits, time.perf_counter())

    def since(self, snap):
        p, s, h, t = snap
        return {"programs_compiled": self.programs - p,
                "persistent_cache_hits": self.cache_hits - h,
                "compile_seconds": round(self.seconds - s, 2),
                "wall_seconds": round(time.perf_counter() - t, 2)}


def _memory(device=None):
    from paddle_tpu import device as D

    st = D.memory_stats(device)
    return {"bytes_in_use": st.get("bytes_in_use"),
            "peak_bytes_in_use": st.get("peak_bytes_in_use"),
            "bytes_limit": st.get("bytes_limit")}


def _timed(fn):
    """fn() -> (its result, wall seconds). The callers' results are host
    values, so the device has finished."""
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def _steps_with_dump(step, ids, steps, hlo_dir):
    """``steps`` calls of a fresh TrainStep with FLAGS_dump_hlo on, the
    repo's own way to see what was compiled -> (losses, wall seconds of each
    call, the first with its compile; optimized HLO)."""
    import paddle_tpu as P

    os.makedirs(hlo_dir, exist_ok=True)
    for n in os.listdir(hlo_dir):
        if "_train_step." in n:
            os.remove(os.path.join(hlo_dir, n))
    P.set_flags({"FLAGS_dump_hlo": hlo_dir})
    try:
        timed = [_timed(lambda: float(step(P.to_tensor(ids)).numpy()))
                 for _ in range(steps)]
    finally:
        P.set_flags({"FLAGS_dump_hlo": ""})
    losses, seconds = [x for x, _ in timed], [t for _, t in timed]
    check(step._compiled._cache_size() == 1, f"{steps} calls of one batch "
          f"compiled the train step {step._compiled._cache_size()} times")
    names = [n for n in os.listdir(hlo_dir)
             if n.endswith("_train_step.optimized.txt")]
    check(len(names) == 1,
          f"FLAGS_dump_hlo wrote {names} for one train step in {hlo_dir}")
    with open(os.path.join(hlo_dir, names[0])) as f:
        return losses, seconds, f.read()


# ------------------------------------------------------------------ device
def phase_device():
    import jax

    devs = jax.devices()
    d = devs[0]
    out = {"phase": "device", "platform": d.platform, "kind": d.device_kind,
           "count": len(devs), "memory": _memory(d)}
    if d.platform == "tpu":
        check(out["memory"]["bytes_limit"], "memory_stats() has no bytes_limit")
    return out


# ----------------------------------------------------------------- kernels
def phase_kernels(seq=2048, head_dim=128, heads=8, tol=2e-2):
    """Flash attention through its public entry (custom_vjp and block
    dispatch included), compiled for the chip, against ``_ref_fwd_impl`` and
    its ``jax.vjp`` on the same bf16 inputs in float32. ``tol`` is relative to
    the largest reference value: bf16 outputs round at 2**-9 of theirs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import flash_attention as fa

    scale = head_dim ** -0.5
    out = {"phase": "kernels", "seq": seq, "head_dim": head_dim,
           "dtype": "bfloat16", "causal": True, "tol_rel_to_max": tol}
    for name, kv_heads in (("mha", heads), ("gqa", heads // 4)):
        rep = heads // kv_heads
        ks = jax.random.split(jax.random.key(SEED), 4)
        q = jax.random.normal(ks[0], (1, seq, heads, head_dim), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, seq, kv_heads, head_dim), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, seq, kv_heads, head_dim), jnp.bfloat16)
        g = jax.random.normal(ks[3], (1, seq, heads, head_dim), jnp.bfloat16)

        def kernel(q, k, v):
            return fa.flash_attention_fwd(q, k, v, causal=True)

        def kernel_grads(q, k, v, g):
            o, vjp = jax.vjp(kernel, q, k, v)
            return (o,) + vjp(g)

        def ref(q, k, v):
            # [1, S, H, D] -> [H, S, D] float32, KV heads repeated
            qb, kb, vb = (jnp.moveaxis(x[0].astype(jnp.float32), 1, 0)
                          for x in (q, k, v))
            kb, vb = (jnp.repeat(x, rep, axis=0) for x in (kb, vb))
            o = fa._ref_fwd_impl(qb, kb, vb, True, scale)[0]
            return jnp.moveaxis(o, 0, 1)[None]

        def ref_grads(q, k, v, g):
            o, vjp = jax.vjp(ref, q, k, v)
            return (o,) + vjp(g.astype(jnp.float32))

        jitted = jax.jit(kernel_grads)
        check("tpu_custom_call" in jitted.lower(q, k, v, g).as_text(),
              f"{name}: no tpu_custom_call in the lowered flash attention")
        got = jitted(q, k, v, g)
        want = jax.jit(ref_grads)(q, k, v, g)
        errs = {}
        for label, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b.astype(jnp.float32))
            check(np.isfinite(a).all(), f"{name} {label}: not finite")
            errs[label] = float(np.abs(a - b).max() / max(np.abs(b).max(), 1.0))
            check(errs[label] <= tol,
                  f"{name} {label}: error {errs[label]:.3g} of the largest "
                  f"reference value, tolerance {tol}")
        out[name] = {"kv_heads": kv_heads, "max_err_rel_to_max": errs}
    return out


# ------------------------------------------------------------------- train
def _build_train(spec):
    import paddle_tpu as P
    from paddle_tpu.models import (
        LlamaConfig,
        LlamaForCausalLM,
        LlamaPretrainingCriterion,
    )

    P.seed(SEED)
    cfg = LlamaConfig(**spec["model"])
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    opt = P.optimizer.AdamW(learning_rate=spec["lr"],
                            parameters=model.parameters(),
                            multi_precision=True)
    crit = LlamaPretrainingCriterion()
    return cfg, model, opt, (lambda m, ids: crit(m(ids), ids))


def _batch(cfg, batch, seq):
    import numpy as np

    return np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def _config_line(cfg, n_params):
    return {"hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "head_dim": cfg.head_dim, "intermediate": cfg.intermediate_size,
            "vocab": cfg.vocab_size, "layers": cfg.num_hidden_layers,
            "dtype": cfg.dtype, "params": int(n_params)}


def phase_train(spec, hlo_dir, meter):
    """A few steps through ``TrainStep.__call__``, then the same steps from
    the same seed as one ``run_steps`` window: the loss is finite, falls on
    the repeated batch, and the two entry points agree."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.device import on_tpu

    batch, seq, steps = spec["batch"], spec["seq"], spec["steps"]
    snap = meter.snapshot()
    cfg, model, opt, loss_fn = _build_train(spec)
    n_params = model.num_params
    ids = _batch(cfg, batch, seq)
    step = P.jit.TrainStep(model, loss_fn, opt)
    by_call, call_seconds, text = _steps_with_dump(step, ids, steps, hlo_dir)
    # the kernel is known to be in the step, not inferred to be; on the CPU
    # the platform chooses the reference and it must not be
    kernel_in_step = "tpu_custom_call" in text
    check(kernel_in_step == on_tpu(),
          f"tpu_custom_call in the compiled train step: {kernel_in_step}, "
          f"on a TPU: {on_tpu()}")
    mem_call = _memory()

    del step, opt, model, loss_fn
    gc.collect()
    cfg, model, opt, loss_fn = _build_train(spec)
    step = P.jit.TrainStep(model, loss_fn, opt)
    stack = P.to_tensor(jnp.broadcast_to(jnp.asarray(ids), (steps, batch, seq)))

    def window():
        return [float(x) for x in step.run_steps(stack).numpy()]

    by_window, window_seconds = _timed(window)     # with its compile
    next_window, next_seconds = _timed(window)     # compiled already
    mem = _memory()
    del step, opt, model, loss_fn, stack
    gc.collect()

    check(all(np.isfinite(by_call + by_window + next_window)),
          f"loss not finite: {by_call} {by_window} {next_window}")
    check(by_call[-1] < by_call[0] and by_window[-1] < by_window[0],
          f"loss did not fall on a repeated batch: {by_call} {by_window}")
    gap = max(abs(a - b) for a, b in zip(by_call, by_window))
    check(gap <= spec["entry_tol"],
          f"__call__ and run_steps disagree by {gap:.4g} "
          f"(tolerance {spec['entry_tol']}): {by_call} {by_window}")
    return {"phase": "train", "config": _config_line(cfg, n_params),
            "reduced": spec["reduced"], "batch": batch, "seq": seq,
            "optimizer": "AdamW(multi_precision=True)", "recompute": True,
            "losses_call": by_call, "losses_run_steps": by_window,
            "losses_next_window": next_window,
            "entry_points_max_gap": gap, "entry_tol": spec["entry_tol"],
            "call_seconds": call_seconds,
            "run_steps_seconds": [window_seconds, next_seconds],
            "tpu_custom_call_in_step": kernel_in_step,
            "memory_after_call": mem_call, "memory": mem,
            **meter.since(snap)}


# ------------------------------------------------------------------- serve
def _prompt(rng, vocab, n):
    return rng.randint(1, vocab, (n,)).tolist()


def _drive(fe, until=None, max_steps=4000):
    for _ in range(max_steps):
        if (until() if until else not fe.pending):
            return
        fe.step()
    raise CheckFailed(f"frontend did not get there in {max_steps} steps")


def _check_tokens(fwd, s_ref, prompt, result, tol):
    """Teacher forcing: the model's own forward over prompt + emitted tokens
    gives the reference logits at every position that emitted one. Each
    emitted token must be the reference's best within ``tol`` (greedy, so a
    larger gap is a wrong answer and not a near-tie), and the engine's own
    logprob of it must match the reference's within ``tol``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as P

    toks = list(result.tokens)
    full = prompt + toks
    ids = np.zeros((1, s_ref), np.int32)
    ids[0, :len(full)] = full
    logits = fwd(P.to_tensor(ids))._value[0]
    rows = logits[len(prompt) - 1:len(full) - 1].astype(jnp.float32)
    ref = np.asarray(jax.nn.log_softmax(rows, axis=-1))
    check(np.isfinite(ref).all(), "reference logits not finite")
    tok_lp = ref[np.arange(len(toks)), toks]
    gap = ref.max(axis=-1) - tok_lp
    lp_err = np.abs(np.asarray(result.logprobs, np.float32) - tok_lp)
    check(gap.max() <= tol, f"rid {result.rid}: an emitted token is "
          f"{gap.max():.3g} nats below the reference's best (tolerance {tol})")
    check(lp_err.max() <= tol, f"rid {result.rid}: engine and reference "
          f"logprobs differ by {lp_err.max():.3g} (tolerance {tol})")
    return {"prompt": len(prompt), "new": len(toks),
            "argmax_agree": float((gap == 0).mean()),
            "max_gap_nats": float(gap.max()),
            "max_logprob_err": float(lp_err.max())}


def _program_counts(eng):
    return {name: fn._cache_size() for name, fn in eng._programs.items()
            if hasattr(fn, "_cache_size")}


def phase_serve(spec, meter):
    """Staggered requests through ServingFrontend -> ServingEngine, then one
    speculative engine; outputs against the model's forward."""
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    snap = meter.snapshot()
    P.seed(SEED)
    cfg = LlamaConfig(**spec["model"])
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    model.eval()
    n_params = model.num_params
    rng = np.random.RandomState(SEED)
    tol = spec["logit_tol"]

    eng = ServingEngine(model, **spec["engine"])
    fe = ServingFrontend([eng])
    sizes = [spec["first"]] + list(spec["staggered"]) + [spec["last"]]
    prompts = [_prompt(rng, cfg.vocab_size, n) for n, _ in sizes]

    def submit(i):
        return fe.submit(prompts[i], max_new_tokens=sizes[i][1], logprobs=True)

    # alone: prefill through the single-step program, then pure-decode
    # megasteps; the next two arrive while it decodes, so their prompts go
    # through the mixed-phase scan; the last arrives at an idle engine
    rids = [submit(0)]
    _drive(fe, until=lambda: eng.megasteps >= 1)
    check(eng.megasteps_mixed == 0, "mixed-phase ran with one request")
    rids += [submit(i) for i in range(1, len(sizes) - 1)]
    _drive(fe)
    rids.append(submit(len(sizes) - 1))
    _, last_seconds = _timed(lambda: _drive(fe))     # every program compiled
    programs = _program_counts(eng)
    counters = {"megasteps": eng.megasteps,
                "megasteps_mixed": eng.megasteps_mixed,
                "megastep_tokens": eng.megastep_tokens,
                "prefill_chunks": eng.prefill_chunks,
                "prefill_tokens_computed": eng.prefill_tokens_computed,
                "phase_seconds": {k: round(v, 3)
                                  for k, v in eng.phase_seconds.items()}}
    check(programs.get("step", 0) >= 1, "single-step program never ran")
    check(eng.megasteps > eng.megasteps_mixed, "pure-decode megastep never ran")
    check(eng.megasteps_mixed >= 1, "mixed-phase megastep never ran")
    results = [fe.result(r) for r in rids]
    for res, (_, new) in zip(results, sizes):
        check(res.ok and len(res.tokens) == new,
              f"rid {res.rid}: {res.status} with {len(res.tokens)}/{new} tokens")
    mem = _memory()
    del fe, eng
    gc.collect()

    # The verify program. N-gram drafts need the stream to revisit its own
    # history, which a model with random weights does not do by itself, even
    # on a prompt that repeats. Its first token barely depends on one more
    # token far back, so: serve the prompt, put the token it emitted in
    # front, serve again, and the new stream's tail recurs at position 0.
    n, new = spec["spec_request"]
    period = _prompt(rng, cfg.vocab_size, spec["spec_period"])
    spec_prompt = (period * (n // len(period) + 1))[:n]
    seng = ServingEngine(model, spec_k=spec["spec_k"], **spec["engine"])
    sfe = ServingFrontend([seng])
    spec_prompts, spec_results = [], []
    for _ in range(3):
        rid = sfe.submit(spec_prompt, max_new_tokens=new, logprobs=True)
        _drive(sfe)
        res = sfe.result(rid)
        check(res.ok and len(res.tokens) == new, f"spec request: {res.status}")
        spec_prompts.append(spec_prompt)
        spec_results.append(res)
        if seng.spec_verify_forwards:
            break
        spec_prompt = res.tokens[:1] + spec_prompt
    check(seng.spec_verify_forwards >= 1, "spec-verify program never ran")
    spec_counters = {"spec_k": seng.spec_k, "requests": len(spec_results),
                     "verify_forwards": seng.spec_verify_forwards,
                     "drafted": seng.spec_draft_tokens,
                     "accepted": seng.spec_accepted_tokens,
                     "programs": _program_counts(seng)}
    del sfe, seng
    gc.collect()

    # reference: the training-side forward (flash attention on the chip),
    # compiled once at one padded length
    prompts += spec_prompts
    results += spec_results
    longest = max(len(p) + len(r.tokens) for p, r in zip(prompts, results))
    s_ref = -(-longest // 256) * 256
    fwd = P.jit.to_static(model)
    checks = [_check_tokens(fwd, s_ref, p, r, tol)
              for p, r in zip(prompts, results)]
    tokens_served = sum(len(r.tokens) for r in results)
    mem_end = _memory()
    del fwd, model
    gc.collect()
    return {"phase": "serve", "config": _config_line(cfg, n_params),
            "reduced": spec["reduced"], "engine": spec["engine"],
            "requests": len(checks), "tokens_served": tokens_served,
            "last_request_seconds": last_seconds,
            "programs": programs, "counters": counters, "spec": spec_counters,
            "output_check": {"reference": "LlamaForCausalLM.forward on "
                             "prompt + emitted tokens (teacher forcing)",
                             "logit_tol_nats": tol, "per_request": checks},
            "memory_engine": mem, "memory": mem_end, **meter.since(snap)}


# -------------------------------------------------------------------- mesh
def phase_mesh(spec, hlo_dir, meter):
    """dp 2 x mp 2 over four devices in one process, then the same seed and
    batch through the unsharded step on one of them."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec

    import paddle_tpu as P
    import paddle_tpu.distributed as dist
    from paddle_tpu.device import on_tpu
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    batch, seq, steps = spec["batch"], spec["seq"], spec["steps"]
    snap = meter.snapshot()
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs four devices, jax has {len(devs)}")

    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                               "sharding_degree": 1, "sep_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    cfg, model, opt, loss_fn = _build_train(spec)
    n_params = model.num_params
    ids = _batch(cfg, batch, seq)
    dmodel = dist.fleet.distributed_model(model)
    dopt = dist.fleet.distributed_optimizer(opt)
    step = P.jit.TrainStep(dmodel, loss_fn, getattr(dopt, "_inner", dopt))
    sharded, sharded_seconds, text = _steps_with_dump(step, ids, steps, hlo_dir)

    # what a mesh that never ran gets wrong
    w = model.llama.layers[0].self_attn.q_proj.weight._value
    check(w.sharding.spec == PartitionSpec(None, "mp"),
          f"q_proj sharding.spec is {w.sharding.spec}")
    shard_devs = {s.device.id for s in w.addressable_shards}
    check(len(shard_devs) == 4, f"q_proj shards sit on devices {shard_devs}")
    check({tuple(s.data.shape) for s in w.addressable_shards}
          == {(cfg.hidden_size, cfg.hidden_size // 2)},
          "q_proj shards are not column halves")
    per_dev = [_memory(d) for d in devs[:4]]
    if on_tpu():
        used = [m["bytes_in_use"] for m in per_dev]
        check(max(used) <= 2 * min(used),
              f"bytes_in_use is not of one order across devices: {used}")
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    check(collectives["all-reduce"] >= 1,
          "no all-reduce in the sharded train step")
    kernel_in_step = "tpu_custom_call" in text
    check(kernel_in_step == on_tpu(),
          f"tpu_custom_call in the sharded step: {kernel_in_step}")

    spec_str = str(w.sharding.spec)
    del step, dopt, dmodel, opt, model, loss_fn, w
    set_hybrid_communicate_group(None)
    gc.collect()
    cfg, model, opt, loss_fn = _build_train(spec)
    step = P.jit.TrainStep(model, loss_fn, opt)
    single = [float(step(P.to_tensor(ids)).numpy()) for _ in range(steps)]
    del step, opt, model, loss_fn
    gc.collect()

    check(all(np.isfinite(sharded + single)), f"loss not finite: {sharded} {single}")
    check(sharded[-1] < sharded[0], f"sharded loss did not fall: {sharded}")
    gap = max(abs(a - b) for a, b in zip(sharded, single))
    check(gap <= spec["loss_tol"], f"sharded and unsharded losses differ by "
          f"{gap:.4g} (tolerance {spec['loss_tol']}): {sharded} {single}")
    return {"phase": "mesh", "mesh": {"dp": 2, "mp": 2},
            "config": _config_line(cfg, n_params), "reduced": spec["reduced"],
            "batch": batch, "seq": seq,
            "losses_sharded": sharded, "losses_unsharded": single,
            "sharded_call_seconds": sharded_seconds,
            "max_gap": gap, "loss_tol": spec["loss_tol"],
            "q_proj_spec": spec_str,
            "q_proj_shard_devices": sorted(shard_devs),
            "bytes_in_use_per_device": [m["bytes_in_use"] for m in per_dev],
            "peak_bytes_per_device": [m["peak_bytes_in_use"] for m in per_dev],
            "collectives": collectives,
            "tpu_custom_call_in_step": kernel_in_step, **meter.since(snap)}


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp2 x mp2 mesh phase and the "
                         "unsharded step it is compared with")
    args = ap.parse_args(argv)
    device = None
    try:
        # block sizes come from the committed table, not a tuner's file
        check(not os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE"),
              "unset PADDLE_TPU_AUTOTUNE_CACHE for the smoke")
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
        check(d.platform == "tpu",
              f"chip_smoke needs a TPU; jax found {d.platform!r}")
        check(device["count"] >= args.chips,
              f"--chips {args.chips} on a host with {device['count']}")

        from paddle_tpu.jit import use_compile_cache

        meter = CompileMeter()
        hlo_dir = os.path.join(REPO, "chiprun_out", "chip_smoke_hlo")
        emit({"phase": "start", "chips": args.chips, "seed": SEED,
              "compile_cache_dir": use_compile_cache()})
        if args.chips == 4:
            emit(phase_mesh(MESH, hlo_dir, meter))
        else:
            emit(phase_device())
            emit(phase_kernels())
            emit(phase_train(TRAIN, hlo_dir, meter))
            emit(phase_serve(SERVE, meter))
        import paddle_tpu.native as native

        check(native._lib is None, "the smoke loaded the native shm queue")
        emit({"phase": "end", "programs_compiled": meter.programs,
              "persistent_cache_hits": meter.cache_hits,
              "compile_seconds": round(meter.seconds, 2)})
    except Exception as e:  # the one handler: report, then fail
        traceback.print_exc()
        sys.stdout.flush()
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000],
              "device": device})
        sys.exit(1)
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
