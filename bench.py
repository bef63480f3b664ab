#!/usr/bin/env python
"""Benchmark driver entry: Llama pretrain step on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Metric: Llama pretrain tokens/sec/chip (BASELINE.json headline) on a
~1B-class decoder. vs_baseline is achieved MFU / 0.35 (the north-star MFU
target), since the reference publishes no absolute in-tree numbers
(BASELINE.md).

One geometry per process, because ~13.5 GB of params + optimizer state per
geometry cannot share a 16 GB chip and a process that holds the chip cannot
hand it to a child. ``PADDLE_TPU_BENCH_HEADS`` selects it:
- 10 (default): head_dim=256, the MXU-shaped config every round since r2
  reports, kept for cross-round comparability;
- 20: head_dim=128, real Llama attention geometry.

It measures the chip or fails: no accelerator, a device it has no peak for,
or any error exits non-zero and prints no result.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

# Peak dense bf16 FLOP/s of one chip, keyed by jax's device_kind.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def run_config(heads: int, batch: int, seq: int, steps: int, loss_mode: str):
    import paddle_tpu as P
    from paddle_tpu.models import (
        LlamaConfig,
        LlamaForCausalLM,
        LlamaPretrainingCriterion,
    )

    P.seed(0)
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2560, intermediate_size=8192,
        num_hidden_layers=9, num_attention_heads=heads,
        max_position_embeddings=2048, dtype="bfloat16", recompute=True,
    )
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    n_params = model.num_params
    opt = P.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                            multi_precision=True)
    # loss path: "unfused" materializes [N, vocab] logits (faster at batch 8:
    # XLA fuses the softmax; measured 0.435 vs 0.399 MFU for chunked, r5);
    # "fused" streams the lm head in chunks (−3GB HBM, for larger batches)
    if loss_mode == "fused":
        n_chunks = int(os.environ.get("PADDLE_TPU_BENCH_CHUNKS",
                                      max(8, (batch * seq) // 2048)))
        loss_fn = lambda m, ids: m.pretraining_loss(ids, n_chunks=n_chunks)  # noqa: E731
    else:
        crit = LlamaPretrainingCriterion()
        loss_fn = lambda m, ids: crit(m(ids), ids)  # noqa: E731
    step = P.jit.TrainStep(model, loss_fn, opt)

    ids = P.to_tensor(np.random.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    if os.environ.get("PADDLE_TPU_BENCH_MULTI", "1") == "1":
        # whole window as ONE compiled scan (TrainStep.run_steps): per-
        # dispatch host/marshalling overhead paid once, like a real loop
        stack = P.to_tensor(jnp.broadcast_to(ids._value, (steps, *ids._value.shape)))
        loss = step.run_steps(stack)[-1:]
        loss.numpy()
        t0 = time.perf_counter()
        losses = step.run_steps(stack)
        loss = losses[-1:]
        float(loss.numpy()[0])
        dt = (time.perf_counter() - t0) / steps
    else:
        loss = step(ids)  # compile + warmup
        loss.numpy()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids)
        float(loss.numpy())  # sync
        dt = (time.perf_counter() - t0) / steps

    tokens_per_sec = batch * seq / dt
    # 6ND per token (fwd+bwd) + attention term
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq * 0.5
    return {
        "tokens_per_sec": tokens_per_sec,
        "flops_per_sec": tokens_per_sec * flops_per_token,
        "dt": dt,
        "loss": float(np.asarray(loss.numpy()).reshape(-1)[-1]),
        "params": n_params,
        "cfg": cfg,
    }


def main():
    from paddle_tpu.device import on_tpu
    from paddle_tpu.jit import use_compile_cache

    dev = jax.devices()[0]
    if not on_tpu():
        sys.exit(f"bench.py measures a TPU chip; jax found {dev.platform!r} "
                 f"({dev.device_kind})")
    peak = PEAK_BF16_FLOPS.get(dev.device_kind)
    if peak is None:
        sys.exit(f"bench.py has no bf16 peak for device_kind "
                 f"{dev.device_kind!r}; add it to PEAK_BF16_FLOPS with its "
                 "source before reporting an MFU")
    use_compile_cache()

    heads = int(os.environ.get("PADDLE_TPU_BENCH_HEADS", 10))
    batch = int(os.environ.get("PADDLE_TPU_BENCH_BATCH", 8))
    seq, steps = 2048, 20
    loss_mode = os.environ.get("PADDLE_TPU_BENCH_LOSS", "unfused")

    r = run_config(heads, batch, seq, steps, loss_mode)
    if not np.isfinite(r["loss"]):
        sys.exit(f"bench.py: loss is not finite ({r['loss']})")
    cfg = r["cfg"]
    mfu = r["flops_per_sec"] / peak
    print(json.dumps({
        "metric": "llama_pretrain_tokens_per_sec_per_chip",
        "value": round(r["tokens_per_sec"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "extra": {
            "backend": dev.platform,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "params": r["params"],
            "batch": batch,
            "seq_len": seq,
            "step_ms": round(r["dt"] * 1e3, 2),
            "mfu": round(mfu, 4),
            "peak_bf16_flops": peak,
            "loss": r["loss"],
            # workload identity so cross-round comparisons (tools/perf_gate.py)
            # can FAIL on mismatched configs instead of comparing apples/oranges
            "workload": {
                "heads": cfg.num_attention_heads,
                "hidden": cfg.hidden_size,
                "layers": cfg.num_hidden_layers,
                "batch": batch,
                "loss_mode": loss_mode,
            },
        },
    }))


if __name__ == "__main__":
    main()
