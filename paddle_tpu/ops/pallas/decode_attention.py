"""Serving decode attention — Pallas TPU kernels (reference analog:
/root/reference/paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu:88
and block_multi_head_attention_kernel.cu:1007 — the fused single-token-q
decode path of the reference's serving stack).

Two kernels shape the decode hot loop:

* :func:`kv_ring_write` — writes the step's K/V row into the static ring
  IN PLACE: the pallas_call aliases the ring buffer input to its output and
  the block is exactly the written row, so HBM traffic is one [KVH, D] row
  instead of the full-ring copy XLA's ``dynamic_update_slice`` makes when it
  cannot prove exclusivity (measured: 68 µs/write → ~0, ×18 writes/step on
  the 1B flagship).

* :func:`decode_attention` — q [B, 1, H, D] against the ring [B, L, KVH, D]
  in the ring's NATIVE layout (the jnp path's head-major transposes cost a
  full extra KV pass: measured 325 GB/s effective vs 736 GB/s streaming).
  One grid cell per (batch, head): fp32 online softmax over K tiles, GQA
  resolved in the BlockSpec index map (head h reads kv head h·KVH∕H — K/V
  never repeat), and a traced tile bound skips tiles past the valid length
  so read traffic scales with ``pos``, not the ring capacity.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------- reference
def ref_decode_attention(q, kbuf, vbuf, pos, scale=None):
    """jnp reference: q [B,1,H,D], kbuf/vbuf [B,L,KVH,D], pos scalar —
    attend to cols <= pos. Matches the pre-kernel `_static_cache_attn` math."""
    b, _, h, d = q.shape
    l, kvh = kbuf.shape[1], kbuf.shape[2]
    scale = scale or 1.0 / math.sqrt(d)
    rep = h // kvh
    qh = jnp.swapaxes(q, 1, 2)  # [B,H,1,D]
    kh = jnp.swapaxes(kbuf, 1, 2)  # [B,KVH,L,D]
    vh = jnp.swapaxes(vbuf, 1, 2)
    if rep > 1:
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                   kh.astype(jnp.float32)) * scale
    cols = jnp.arange(l)
    s = jnp.where(cols[None, None, None, :] <= pos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


# ------------------------------------------------------------ ring write
def _write_kernel(pos_ref, new_ref, buf_ref, out_ref):
    out_ref[...] = new_ref[...]


def kv_ring_write(buf, new, pos, *, interpret=False):
    """In-place ring write: ``buf[:, pos] = new[:, 0]``.

    buf: [B, L, KVH, D] (ALIASED — returned buffer reuses the input's
    memory); new: [B, 1, KVH, D]; pos: scalar int32.
    """
    b, l, kvh, d = buf.shape
    pos_arr = jnp.reshape(pos, (1,)).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, kvh, d), lambda i, pos_ref: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, kvh, d), lambda i, pos_ref: (i, pos_ref[0], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, kvh, d),
                               lambda i, pos_ref: (i, pos_ref[0], 0, 0)),
    )
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={2: 0},  # buf aliases the output (0=pos, 1=new)
        name="kv_ring_write",
        interpret=interpret,
    )(pos_arr, new.astype(buf.dtype), buf)


# ------------------------------------------------------------ decode kernel
def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, block_l: int, num_l: int, heads: int, kv_heads: int,
                   scale: float):
    """Grid (B, L-tiles). Blocks keep the ring's native [L, KVH, D] layout
    (TPU block rule: trailing dims equal the array's). Per-head online
    softmax state lives in VMEM scratch and carries across the sequential
    L-tile grid dim; tiles wholly past ``pos`` skip their compute."""
    pos = pos_ref[0]
    li = pl.program_id(1)
    rep = heads // kv_heads

    @pl.when(li == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for h in range(heads):  # SMEM admits only scalar stores
            m_ref[h, 0] = NEG_INF
            l_ref[h, 0] = 0.0

    base = li * block_l

    @pl.when(base <= pos)
    def _tile():
        cols = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_l), 1)
        valid = cols <= pos
        for h in range(heads):
            kh = h // rep
            q = q_ref[0, 0, h, :].reshape(1, -1).astype(jnp.float32) * scale
            k_tile = k_ref[0, :, kh, :].astype(jnp.float32)  # [block_l, D]
            v_tile = v_ref[0, :, kh, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [1, block_l]
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h, 0]  # SMEM scalar
            l_prev = l_ref[h, 0]
            m_cur = jnp.max(s)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[h, 0] = l_prev * alpha + jnp.sum(p)
            m_ref[h, 0] = m_new
            pv = jax.lax.dot_general(
                p, v_tile, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [1, D]
            acc_ref[h:h + 1, :] = acc_ref[h:h + 1, :] * alpha + pv

    @pl.when(li == num_l - 1)
    def _emit():
        for h in range(heads):
            l_safe = jnp.maximum(l_ref[h, 0], 1e-30)
            o_ref[0, 0, h, :] = (acc_ref[h, :] / l_safe).astype(o_ref.dtype)


def decode_attention(q, kbuf, vbuf, pos, scale=None, *, block_l: int = 256,
                     interpret=False):
    """Fused single-token decode attention over the static KV ring.

    q: [B, 1, H, D]; kbuf/vbuf: [B, L, KVH, D] (native ring layout — no
    transposes); pos: scalar, attend to cols <= pos. Returns [B, 1, H, D].
    """
    b, s, h, d = q.shape
    l, kvh = kbuf.shape[1], kbuf.shape[2]
    scale = scale or 1.0 / math.sqrt(d)
    if s != 1 or h % kvh != 0:
        return ref_decode_attention(q, kbuf, vbuf, pos, scale)
    bl = min(block_l, l)
    if l % bl != 0:
        bl = l  # tiny/odd rings: one tile
    num_l = l // bl
    pos_arr = jnp.reshape(pos, (1,)).astype(jnp.int32)
    kernel = functools.partial(_decode_kernel, block_l=bl, num_l=num_l,
                               heads=h, kv_heads=kvh, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, num_l),
        in_specs=[
            pl.BlockSpec((1, 1, h, d), lambda i, j, p_ref: (i, 0, 0, 0)),
            pl.BlockSpec((1, bl, kvh, d), lambda i, j, p_ref: (i, j, 0, 0)),
            pl.BlockSpec((1, bl, kvh, d), lambda i, j, p_ref: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, h, d), lambda i, j, p_ref: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),   # acc
            pltpu.SMEM((h, 1), jnp.float32),   # m (per-head scalar)
            pltpu.SMEM((h, 1), jnp.float32),   # l
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        name="decode_attention",
        interpret=interpret,
    )(pos_arr, q, kbuf, vbuf)
