"""Fused RMSNorm (+ optional residual add) — Pallas TPU kernel.

Replaces the reference's fused_rms_norm CUDA kernel
(/root/reference/paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu
behind python/paddle/incubate/nn/functional/fused_rms_norm.py): one HBM
read of x (+residual), one write of each output — the residual-add and
normalization never round-trip through HBM separately. Backward is the
analytic RMSNorm vjp in jnp (elementwise + one row reduction; XLA fuses).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ...device import on_tpu


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x * inv * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _kernel_residual(x_ref, r_ref, w_ref, o_ref, res_out_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    res_out_ref[...] = x.astype(res_out_ref.dtype)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x * inv * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# Pallas double-buffers every blocked operand in VMEM, and the compiler
# refuses a kernel whose scoped allocation passes 16 MiB. 12 MiB for the
# operand buffers leaves the rest to the kernel's fp32 temporaries.
_VMEM_BLOCK_BUDGET = 12 << 20


def _rows_block(n_rows: int, hidden: int, itemsize: int, n_streams: int) -> int:
    """Largest power-of-two row block (at most 256) that divides ``n_rows``
    and keeps ``n_streams`` double-buffered [rows, hidden] operands inside
    the VMEM budget."""
    per_row = 2 * n_streams * hidden * itemsize
    for b in (256, 128, 64, 32, 16, 8, 4, 2):
        if n_rows % b == 0 and b * per_row <= _VMEM_BLOCK_BUDGET:
            return b
    return 1


def _pallas_rms(x2, w, eps, interpret):
    n, h = x2.shape
    br = _rows_block(n, h, x2.dtype.itemsize, n_streams=2)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2.dtype),
        name="rms_norm",
        interpret=interpret,
    )(x2, w)


def _pallas_rms_residual(x2, r2, w, eps, interpret):
    n, h = x2.shape
    br = _rows_block(n, h, x2.dtype.itemsize, n_streams=4)
    return pl.pallas_call(
        functools.partial(_kernel_residual, eps=eps),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2.dtype),
                   jax.ShapeDtypeStruct((n, h), x2.dtype)],
        name="rms_norm_residual",
        interpret=interpret,
    )(x2, r2, w)


def _ref_rms(x, w, eps):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * w.astype(jnp.float32)).astype(x.dtype)


def _use_kernel(interpret: bool) -> bool:
    return interpret or on_tpu()


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm_fused(x, w, eps: float = 1e-6, interpret: bool = False):
    """x [..., H], w [H] -> same shape; fp32 statistics."""
    out, _ = _fwd(x, w, eps, interpret)
    return out


def _fwd(x, w, eps, interpret):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if _use_kernel(interpret):
        out = _pallas_rms(x2, w, eps, interpret).reshape(shape)
    else:
        out = _ref_rms(x, w, eps)
    return out, (x, w)


def _bwd(eps, interpret, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xf * inv
    gw = jnp.sum((gf * xhat).reshape(-1, x.shape[-1]), axis=0).astype(w.dtype)
    gx_hat = gf * wf
    dx = inv * (gx_hat - xhat * jnp.mean(gx_hat * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), gw


rms_norm_fused.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rms_norm_residual_fused(x, residual, w, eps: float = 1e-6, interpret: bool = False):
    """-> (normed, residual_out) with residual_out = x + residual fused in."""
    out, _ = _fwd_res(x, residual, w, eps, interpret)
    return out


def _fwd_res(x, residual, w, eps, interpret):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r2 = residual.reshape(-1, shape[-1])
    if _use_kernel(interpret):
        out, res_out = _pallas_rms_residual(x2, r2, w, eps, interpret)
        out, res_out = out.reshape(shape), res_out.reshape(shape)
    else:
        s = x + residual
        out, res_out = _ref_rms(s, w, eps), s
    return (out, res_out), (x, residual, w)


def _bwd_res(eps, interpret, res, gs):
    x, residual, w = res
    g_out, g_res = gs
    # keep the recomputed pre-norm stream in fp32: the forward's statistics
    # were computed from the fp32 sum
    s = x.astype(jnp.float32) + residual.astype(jnp.float32)
    dx, gw = _bwd(eps, interpret, (s, w), g_out)
    dsum = dx.astype(jnp.float32) + g_res.astype(jnp.float32)
    return dsum.astype(x.dtype), dsum.astype(residual.dtype), gw


rms_norm_residual_fused.defvjp(_fwd_res, _bwd_res)
