"""Weight-only int8 matmul — Pallas TPU kernel.

Reference analog: the int8 weight-only GEMM tier
(/root/reference/paddle/phi/kernels/fusion/cutlass/ + the weight_only_linear
op behind python/paddle/nn/quant/). Serving-path motivation: weights stream
from HBM at 1 byte/element (half the bf16 traffic) and are dequantized
per-tile in VMEM right before the MXU — the memory win of int8 storage
without writing a dequantized copy back to HBM.

x [M, K] (bf16/f32) @ qw [K, N] (int8, per-out-channel scales [N]) -> [M, N].
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import on_tpu

__all__ = ["int8_matmul"]


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [bm, bk]
    w = q_ref[...].astype(x.dtype)  # dequant int8 tile in VMEM (scale at end)
    # the package pins jax_default_matmul_precision=highest; Mosaic refuses
    # that fp32 contraction on bf16 operands ("Bad lhs type"), and there is
    # nothing in them for it to recover
    prec = jax.lax.Precision.DEFAULT if x.dtype == jnp.bfloat16 else None
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        s = s_ref[...].astype(jnp.float32)  # [1, bn]
        o_ref[...] = (acc_ref[...] * s).astype(o_ref.dtype)


def _pick(n: int, target: int) -> int:
    b = min(target, n)
    while n % b:
        b //= 2
    return max(b, 1)


def _use_kernel(m, k, n, interpret) -> bool:
    return ((interpret or on_tpu())
            and m % 8 == 0 and k % 128 == 0 and n % 128 == 0)


def _int8_mm_impl(x2, qw, scale, interpret):
    m, k = x2.shape
    n = qw.shape[1]
    if not _use_kernel(m, k, n, interpret):
        return x2 @ (qw.astype(x2.dtype) * scale.astype(x2.dtype)[None, :])
    bm = _pick(m, 512)
    bk = _pick(k, 512)
    bn = _pick(n, 512)
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
            # scales ride as [1, N]: a blocked 1-D operand gets a Mosaic
            # layout (T(bn)) that XLA's own (T(1024)) does not match
            pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="int8_matmul",
        interpret=interpret,
    )(x2, qw, scale.reshape(1, n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _int8_mm(x2, qw, scale, interpret):
    return _int8_mm_impl(x2, qw, scale, interpret)


def _int8_mm_fwd(x2, qw, scale, interpret):
    return _int8_mm_impl(x2, qw, scale, interpret), (qw, scale)


def _int8_mm_bwd(interpret, res, g):
    qw, scale = res
    # dx = g @ W^T with W dequantized on the fly; weights are frozen int8
    # storage (fine-tune-over-quantized pattern) so their cotangent is zero
    w = qw.astype(g.dtype) * scale.astype(g.dtype)[None, :]
    dx = g @ w.T
    d_qw = np.zeros(qw.shape, dtype=jax.dtypes.float0)
    return dx, d_qw, jnp.zeros_like(scale)


_int8_mm.defvjp(_int8_mm_fwd, _int8_mm_bwd)


def int8_matmul(x, qw, scale, interpret: bool = False):
    """x [..., K] @ qw [K, N] int8 * scale [N] -> [..., N]. Differentiable
    w.r.t. x (dequantized transpose matmul in the backward).

    Small/odd row counts (autoregressive decode: m = batch) are zero-padded
    to the 8-row sublane so the int8-streaming kernel still serves them —
    the dense-dequant fallback would re-materialize the full bf16 weight."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    m = x2.shape[0]
    pad = (-m) % 8
    if pad and _use_kernel(m + pad, x2.shape[1], qw.shape[1], interpret):
        out = _int8_mm(jnp.concatenate(
            [x2, jnp.zeros((pad, x2.shape[1]), x2.dtype)]), qw, scale, interpret)[:m]
    else:
        out = _int8_mm(x2, qw, scale, interpret)
    return out.reshape(*orig_shape[:-1], qw.shape[1])
