"""Flash-attention block-size autotuning.

Reference analog: the kernel autotune cache + timing harness
(/root/reference/paddle/phi/kernels/autotune/switch_autotune.h, cache.h) that
picks cudnn/cutlass algorithms by measurement. Here the tunable is the
(block_q, block_k) tiling of the Pallas flash kernels.

Two tiers:
  * a measured default table (tuned on TPU v5e, see ``tune()``) keyed by
    (kind, seq bucket, head_dim) — zero-cost lookup, always available;
  * optional on-line measurement: ``paddle.set_flags({'FLAGS_flash_autotune':
    True})`` times every candidate on first encounter of a new shape key
    (eager, cached for the process, persisted to
    ``PADDLE_TPU_AUTOTUNE_CACHE`` if set).
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ...device import on_tpu

__all__ = ["get_flash_blocks", "tune", "clear_cache"]


def _pick_block(s: int, target: int) -> int:
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


def _bucket_seq(s: int) -> int:
    """Round down to a power of two (tables are per-magnitude, not per-shape)."""
    b = 1
    while b * 2 <= s:
        b *= 2
    return b


# Measured on TPU v5e-1 via tune() with in-graph iteration loops (bf16,
# causal, seq 2048, head_dim 128: fwd 256x256 ≈ 9.2ms vs 512x512 10.4ms;
# bwd within noise of each other — keep 256x256). Values are *targets* —
# _pick_block snaps them to divisors of the actual seq.
_DEFAULT_TARGETS: Dict[Tuple[str, int], Tuple[int, int]] = {
    ("fwd", 128): (256, 256),
    ("bwd", 128): (256, 256),
    ("fwd", 64): (256, 256),
    ("bwd", 64): (256, 256),
    # large head_dim: smaller tiles keep K/V + fp32 staging inside VMEM
    ("fwd", 256): (256, 256),
    ("bwd", 256): (128, 256),
    ("fwd", 512): (128, 128),
    ("bwd", 512): (128, 128),
}

# process-level measured cache: (kind, sq_bucket, sk_bucket, d) -> (bq, bk)
_measured: Dict[Tuple, Tuple[int, int]] = {}
_cache_loaded = False


def _cache_path():
    return os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")


def _load_cache():
    global _cache_loaded
    if _cache_loaded:
        return
    _cache_loaded = True
    p = _cache_path()
    if p and os.path.exists(p):
        # a cache the user named and the program cannot read is an error:
        # falling back to the table would run other block sizes than asked
        with open(p) as f:
            for k, v in json.load(f).items():
                _measured[tuple(json.loads(k))] = tuple(v)


def _save_cache():
    p = _cache_path()
    if not p:
        return
    with open(p, "w") as f:
        json.dump({json.dumps(list(k)): list(v) for k, v in _measured.items()}, f)


def clear_cache():
    _measured.clear()


def get_flash_blocks(kind: str, sq: int, sk: int, d: int) -> Tuple[int, int]:
    """Block sizes for the flash kernel. kind: 'fwd' | 'bwd'."""
    _load_cache()
    key = (kind, _bucket_seq(sq), _bucket_seq(sk), d)
    hit = _measured.get(key)
    if hit is not None:
        return _pick_block(sq, hit[0]), _pick_block(sk, hit[1])

    from ...framework.flags import flag_value

    try:
        autotune_on = flag_value("flash_autotune")
    except KeyError:  # flags module import cycle during bootstrap
        autotune_on = False
    if autotune_on and on_tpu():
        bq, bk = _measure(kind, sq, sk, d)
        _measured[key] = (bq, bk)
        _save_cache()
        return _pick_block(sq, bq), _pick_block(sk, bk)

    tq, tk = _DEFAULT_TARGETS.get((kind, d), (512, 512) if kind == "fwd" else (256, 256))
    return _pick_block(sq, tq), _pick_block(sk, tk)


def _candidates(kind: str, sq: int, sk: int):
    opts = [128, 256, 512, 1024]
    for bq in opts:
        for bk in opts:
            if sq % bq == 0 and sk % bk == 0 and bq * bk <= 512 * 1024:
                yield bq, bk


def _measure(kind: str, sq: int, sk: int, d: int, n_iter: int = 20) -> Tuple[int, int]:
    """Time candidates with an IN-GRAPH iteration loop: each candidate runs
    ``n_iter`` chained kernel invocations inside one jit dispatch, so
    per-dispatch latency and async readback cannot corrupt the
    measurement."""
    from jax import lax

    from . import flash_attention as fa

    bh = 8
    rng = jax.random.key(0)
    q = jax.random.normal(rng, (bh, sq, d), jnp.bfloat16)
    k = jax.random.normal(rng, (bh, sk, d), jnp.bfloat16)
    v = jax.random.normal(rng, (bh, sk, d), jnp.bfloat16)
    scale = 1.0 / (d ** 0.5)

    def run_chained(body):
        f = jax.jit(lambda x: lax.fori_loop(0, n_iter, lambda i, x: body(x), x))
        out = f(q)
        float(out.reshape(-1)[0])  # warm + sync
        t0 = time.perf_counter()
        out = f(q)
        float(out.reshape(-1)[0])
        return (time.perf_counter() - t0) / n_iter

    best, best_t = None, float("inf")
    if kind != "fwd":
        o, lse = fa._pallas_fwd(q, k, v, True, scale,
                                _pick_block(sq, 256), _pick_block(sk, 256), False)
        g = jnp.ones_like(o)
    for bq, bk in _candidates(kind, sq, sk):
        try:
            if kind == "fwd":
                dt = run_chained(lambda x, bq=bq, bk=bk: fa._pallas_fwd(
                    x, k, v, True, scale, bq, bk, False)[0].astype(q.dtype))
            else:
                dt = run_chained(lambda x, bq=bq, bk=bk: fa._pallas_bwd(
                    x, k, v, o, lse, g, True, scale, bq, bk,
                    False)[0].astype(q.dtype))
            if dt < best_t:
                best, best_t = (bq, bk), dt
        except Exception:
            continue
    return best or (_pick_block(sq, 256), _pick_block(sk, 256))


def tune(seqs=(1024, 2048, 4096, 8192), head_dims=(64, 128), verbose=True):
    """Offline tuner: measure all (kind, seq, head_dim) combos and return the
    results table (also fills the in-process cache)."""
    out = {}
    for d in head_dims:
        for s in seqs:
            for kind in ("fwd", "bwd"):
                bq, bk = _measure(kind, s, s, d)
                _measured[(kind, _bucket_seq(s), _bucket_seq(s), d)] = (bq, bk)
                out[(kind, s, d)] = (bq, bk)
                if verbose:
                    print(f"tune {kind} seq={s} d={d}: block_q={bq} block_k={bk}")
    _save_cache()
    return out
