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


# Measured on TPU v5e-1 via tune() with in-graph iteration loops. head_dim 128
# swept in PR 33 for the kernels that feed the MXU bf16 tiles, at the Mistral
# train cell's call (tune((2048,), (128,), bh=128, kv_rep=4): q [128, 2048,
# 128] bf16 over 32 KV heads, causal), ms a call, block_q x block_k:
#   fwd  512x512 2.04 | 1024x512 2.20 | 1024x256 2.32 | 512x256 2.35 |
#        512x1024 2.35 | 256x512 3.17 | 256x256 3.38 | 128x128 6.96
#   bwd  512x512 5.17 | 1024x512 5.67 | 512x256 5.68 | 512x1024 5.77 |
#        256x512 6.06 | 256x256 7.01 | 128x128 12.77
# With one-pass products a tile's fixed work (the running max and sum, the
# rescale of the accumulator, the loop turn) sets the pace, so larger tiles
# win until the masked half of the diagonal tiles costs more (1024). The
# float32 kernels before read fwd 256x256 9.2 against 512x512 10.4. Other head
# sizes keep what was swept for those. Values are *targets* — _pick_block
# snaps them to divisors of the actual seq.
_DEFAULT_TARGETS: Dict[Tuple[str, int], Tuple[int, int]] = {
    ("fwd", 128): (512, 512),
    ("bwd", 128): (512, 512),
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


def _time_candidates(kind: str, sq: int, sk: int, d: int, n_iter: int = 20,
                     bh: int = 8, kv_rep: int = 1) -> Dict[Tuple[int, int], float]:
    """Seconds a call of every candidate the compiler takes, by an IN-GRAPH
    iteration loop: each candidate runs ``n_iter`` chained kernel invocations
    inside one jit dispatch, so per-dispatch latency and async readback cannot
    corrupt the measurement. ``bh`` query heads over ``bh // kv_rep`` KV heads,
    bf16, causal."""
    from jax import lax

    from . import flash_attention as fa

    rng = jax.random.key(0)
    q = jax.random.normal(rng, (bh, sq, d), jnp.bfloat16)
    k = jax.random.normal(rng, (bh // kv_rep, sk, d), jnp.bfloat16)
    v = jax.random.normal(rng, (bh // kv_rep, sk, d), jnp.bfloat16)
    scale = 1.0 / (d ** 0.5)

    def run_chained(body):
        f = jax.jit(lambda x: lax.fori_loop(0, n_iter, lambda i, x: body(x), x))
        out = f(q)
        float(out.reshape(-1)[0])  # warm + sync
        t0 = time.perf_counter()
        out = f(q)
        float(out.reshape(-1)[0])
        return (time.perf_counter() - t0) / n_iter

    def fwd(x, bq, bk):
        return fa._pallas_fwd(x, k, v, True, scale, bq, bk, False, kv_rep=kv_rep)[0]

    if kind != "fwd":
        o, lse = fa._pallas_fwd(q, k, v, True, scale, _pick_block(sq, 256),
                                _pick_block(sk, 256), False, kv_rep=kv_rep)
        g = jnp.ones_like(o)

    def bwd(x, bq, bk):
        # all three gradients reach the carry: an unused one takes its
        # kernel out of the program
        dq, dk, dv = fa._pallas_bwd(x, k, v, o, lse, g, True, scale, bq, bk,
                                    False, kv_rep=kv_rep)
        return dq + jnp.sum(dk + dv).astype(dq.dtype)

    seconds = {}
    for bq, bk in _candidates(kind, sq, sk):
        try:
            seconds[bq, bk] = run_chained(
                functools.partial(fwd if kind == "fwd" else bwd, bq=bq, bk=bk))
        except Exception:  # a tiling the compiler refuses (VMEM) is no candidate
            continue
    return seconds


def _fastest(seconds, sq: int, sk: int) -> Tuple[int, int]:
    if not seconds:
        return _pick_block(sq, 256), _pick_block(sk, 256)
    return min(seconds, key=seconds.get)


def _measure(kind: str, sq: int, sk: int, d: int, **shape) -> Tuple[int, int]:
    return _fastest(_time_candidates(kind, sq, sk, d, **shape), sq, sk)


def tune(seqs=(1024, 2048, 4096, 8192), head_dims=(64, 128), verbose=True,
         bh=8, kv_rep=1):
    """Offline tuner: measure all (kind, seq, head_dim) combos and return the
    results table (also fills the in-process cache). ``bh`` and ``kv_rep`` give
    the call's heads: ``tune((2048,), (128,), bh=128, kv_rep=4)`` is the
    Mistral train cell's."""
    out = {}
    for d in head_dims:
        for s in seqs:
            for kind in ("fwd", "bwd"):
                seconds = _time_candidates(kind, s, s, d, bh=bh, kv_rep=kv_rep)
                bq, bk = _fastest(seconds, s, s)
                _measured[(kind, _bucket_seq(s), _bucket_seq(s), d)] = (bq, bk)
                out[(kind, s, d)] = (bq, bk)
                if verbose:
                    print(f"tune {kind} seq={s} d={d}: block_q={bq} block_k={bk}  "
                          + "  ".join(f"{q}x{k} {t * 1e3:.3f}ms"
                                      for (q, k), t in sorted(seconds.items())))
    _save_cache()
    return out
