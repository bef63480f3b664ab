"""Fused rotary embedding + SwiGLU — Pallas TPU kernels.

Reference analogs: the CUDA fused kernels behind
/root/reference/python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py
and .../swiglu.py (paddle/phi/kernels/fusion/gpu/). Both ops are
HBM-bandwidth bound; the kernels do exactly one read of each input and one
write of each output with fp32 math in VMEM, instead of the
split/concat/mul/add chain the jnp forms lower to.

Rope backward is rope with negated sin (a rotation by -theta), so the same
kernel serves fwd and bwd. SwiGLU backward is a second single-pass kernel
recomputing sigmoid from the saved inputs (no activation stash in HBM).

Kernel or reference form is decided here, from what a call shows
(``_in_kernel``): the Llama trunk's forward calls ``rope_fused`` and
``swiglu_fused`` whatever it runs on.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ...device import on_tpu

__all__ = ["rope_fused", "swiglu_fused"]


def _in_kernel(interpret: bool, dims_ok: bool) -> bool:
    """Whether a call takes its Pallas kernel or its reference form, from what
    it can observe: the dims (``_rows_ok``, ``_dims_ok``), the platform, and
    no hybrid mesh with an axis over 1. Under a mesh GSPMD partitions the
    reference form: a Mosaic call inside a GSPMD program needs a ``shard_map``
    (PR 21), and that form waits for a four-chip train cell (ROADMAP)."""
    from ...distributed.topology import get_hybrid_communicate_group

    if not dims_ok:
        return False
    if interpret:
        return True
    hcg = get_hybrid_communicate_group()
    return on_tpu() and (hcg is None or hcg.mesh.size == 1)


def _rows_ok(rows: int, block: int) -> bool:
    # Mosaic takes a block's rows in whole sublane tiles of 8, or all of them:
    # a prompt of 7 or 100 tokens takes the reference form
    return block % 8 == 0 or block == rows


# ---------------------------------------------------------------- fused rope
def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    c = cos_ref[...].astype(jnp.float32)[:, None, :]  # [block_s, 1, D/2]
    s = sin_ref[...].astype(jnp.float32)[:, None, :]
    xf = x_ref[0].astype(jnp.float32)  # [block_s, H, D]
    half = xf.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    o_ref[0] = out.astype(o_ref.dtype)


def _pick_s_block(s: int, h: int, d: int) -> int:
    # keep the fp32 staging block [bs, h, d] ≤ ~1MB: scoped VMEM holds the
    # bf16 in/out blocks (double-buffered) + fp32 intermediates
    target = max((1 << 20) // max(h * d * 4, 1), 8)
    b = 1
    while b * 2 <= min(target, s):
        b *= 2
    while s % b:
        b //= 2
    return max(b, 1)


def _rope_one_pallas(x, cos, sin, interpret):
    """x [B,S,H,D] — blocks keep H and D whole (TPU last-two-dims rule);
    the grid walks (batch, seq block)."""
    b, s, h, d = x.shape
    bs = _pick_s_block(s, h, d)
    return pl.pallas_call(
        _rope_kernel,
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((bs, d // 2), lambda i, j: (j, 0)),
            pl.BlockSpec((bs, d // 2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, h, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="fused_rope",
        interpret=interpret,
    )(x, cos, sin)


def _rope_pallas(q, k, cos, sin, interpret):
    return (_rope_one_pallas(q, cos, sin, interpret),
            _rope_one_pallas(k, cos, sin, interpret))


def _rope_ref(q, k, cos, sin):
    def rot(x):
        xf = x.astype(jnp.float32)
        half = xf.shape[-1] // 2
        x1, x2 = xf[..., :half], xf[..., half:]
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def rope_fused(q, k, cos, sin, interpret: bool = False):
    """q [B,S,H,D], k [B,S,Hk,D], cos/sin [S, D/2] (already sliced to the
    sequence window) -> rotated (q, k)."""
    out, _ = _rope_fwd(q, k, cos, sin, interpret)
    return out


def _dims_ok(q, k) -> bool:
    s = q.shape[1]
    return (q.shape[-1] % 2 == 0 and s == k.shape[1]
            and all(_rows_ok(s, _pick_s_block(s, x.shape[2], x.shape[3])) for x in (q, k)))


def _rope_fwd(q, k, cos, sin, interpret):
    if _in_kernel(interpret, _dims_ok(q, k)):
        out = tuple(_rope_pallas(q, k, cos, sin, interpret))
    else:
        out = _rope_ref(q, k, cos, sin)
    return out, (cos, sin)


def _rope_bwd(interpret, res, g):
    cos, sin = res
    gq, gk = g
    # d/dx of a rotation by theta is a rotation of the cotangent by -theta
    if _in_kernel(interpret, _dims_ok(gq, gk)):
        dq, dk = _rope_pallas(gq, gk, cos, -sin, interpret)
    else:
        dq, dk = _rope_ref(gq, gk, cos, -sin)
    return dq, dk, None, None


rope_fused.defvjp(_rope_fwd, _rope_bwd)


# ------------------------------------------------------------- fused swiglu
def _swiglu_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (a * jax.nn.sigmoid(a) * b).astype(o_ref.dtype)


def _swiglu_bwd_kernel(a_ref, b_ref, g_ref, da_ref, db_ref):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(a)
    silu = a * sig
    da_ref[...] = (g * b * (sig + silu * (1.0 - sig))).astype(da_ref.dtype)
    db_ref[...] = (g * silu).astype(db_ref.dtype)


def _grid_2d(n: int, h: int):
    # cap each [br, h] bf16 block at ~256KB: the bwd holds 5 io blocks
    # (double-buffered) plus fp32 staging, all inside the 16MB scoped VMEM
    cap = max((256 << 10) // max(h * 2, 1), 8)
    br = 1
    while br * 2 <= min(cap, 256):
        br *= 2
    while n % br:
        br //= 2
    return max(br, 1)


def _swiglu_pallas(a2, b2, interpret):
    n, h = a2.shape
    br = _grid_2d(n, h)
    return pl.pallas_call(
        _swiglu_kernel,
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), a2.dtype),
        name="swiglu_fwd",
        interpret=interpret,
    )(a2, b2)


def _swiglu_bwd_pallas(a2, b2, g2, interpret):
    n, h = a2.shape
    br = _grid_2d(n, h)
    return pl.pallas_call(
        _swiglu_bwd_kernel,
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((n, h), a2.dtype),
                   jax.ShapeDtypeStruct((n, h), b2.dtype)],
        name="swiglu_bwd",
        interpret=interpret,
    )(a2, b2, g2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def swiglu_fused(a, b, interpret: bool = False):
    """silu(a) * b, one HBM pass. a/b any shape with matching dims."""
    out, _ = _swiglu_fwd(a, b, interpret)
    return out


def _swiglu_rows_ok(a) -> bool:
    n = math.prod(a.shape[:-1])
    return _rows_ok(n, _grid_2d(n, a.shape[-1]))


def _swiglu_fwd(a, b, interpret):
    if _in_kernel(interpret, _swiglu_rows_ok(a)):
        shape = a.shape
        out = _swiglu_pallas(a.reshape(-1, shape[-1]), b.reshape(-1, shape[-1]),
                             interpret).reshape(shape)
    else:
        af = a.astype(jnp.float32)
        out = (af * jax.nn.sigmoid(af) * b.astype(jnp.float32)).astype(a.dtype)
    return out, (a, b)


def _swiglu_bwd(interpret, res, g):
    a, b = res
    if _in_kernel(interpret, _swiglu_rows_ok(a)):
        shape = a.shape
        da, db = _swiglu_bwd_pallas(a.reshape(-1, shape[-1]), b.reshape(-1, shape[-1]),
                                    g.reshape(-1, shape[-1]), interpret)
        return da.reshape(shape), db.reshape(shape)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(af)
    silu = af * sig
    da = gf * bf * (sig + silu * (1.0 - sig))
    db = gf * silu
    return da.astype(a.dtype), db.astype(b.dtype)


swiglu_fused.defvjp(_swiglu_fwd, _swiglu_bwd)
