"""Flash attention — Pallas TPU kernels, forward AND backward.

Replaces the reference's FlashAttention2 CUDA dependency
(/root/reference/third_party/flashattn, paddle/phi/kernels/flash_attn_kernel.h)
with TPU kernels: online-softmax tiling in VMEM, fp32 accumulators, MXU
matmuls. Layout is paddle's [batch, seq, heads, head_dim].

Forward: one grid cell per (batch*head, q-block); K/V streamed through a
fori_loop of MXU tiles; emits per-row logsumexp (LSE) for the backward.

Backward (FlashAttention-2 algorithm): two kernels.
  * dQ:  grid (bh, q-block) — recompute P = exp(S - LSE) tile by tile,
         dS = P * (dO·Vᵀ - Δ), dQ += dS·K, where Δ = rowsum(dO ∘ O).
  * dKV: grid (bh, k-block) — same recomputation streaming Q/dO tiles,
         dV += Pᵀ·dO, dK += dSᵀ·Q.
No S×S matrix is ever materialized; memory is O(S·D) like the forward.

Causal masking uses FlashAttention-2's bottom-right alignment
(row + seq_k - seq_q >= col) in every path, so kernel and jnp fallback agree
for seq_q != seq_k. Causal loops skip fully-masked tiles via traced loop
bounds.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ...device import on_tpu
from .autotune import get_flash_blocks

NEG_INF = -1e30


# --------------------------------------------------------------- jnp fallback
def _ref_fwd_impl(q, k, v, causal: bool, scale: float):
    """[BH, S, D] reference with fp32 softmax; returns (out, lse).

    Rows with no visible key (causal with seq_q > seq_k) produce zeros, the
    same convention as the Pallas kernel (FlashAttention-2 behavior)."""
    logits = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    row_valid = None
    if causal:
        sq, sk = logits.shape[1], logits.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
        row_valid = jnp.any(mask, axis=-1)  # [Sq]
    m = jnp.max(logits, axis=-1, keepdims=True)
    p_un = jnp.exp(logits - m)
    l = jnp.sum(p_un, axis=-1, keepdims=True)  # noqa: E741
    lse = (m + jnp.log(l))[..., 0]
    p = (p_un / l).astype(q.dtype)
    if row_valid is not None:
        p = jnp.where(row_valid[None, :, None], p, jnp.zeros((), p.dtype))
    return jnp.einsum("bqk,bkd->bqd", p, v), lse


def _ref_impl(q, k, v, causal: bool, scale: float):
    return _ref_fwd_impl(q, k, v, causal, scale)[0]


def _ref_bwd_impl(q, k, v, o, lse, g, causal: bool, scale: float, delta=None):
    """jnp backward from saved LSE (used on CPU / odd shapes)."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    row_valid = None
    if causal:
        sq, sk = s.shape[1], s.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
        row_valid = jnp.any(mask, axis=-1)
    p = jnp.exp(s - lse[..., None])
    if row_valid is not None:
        # fully-masked rows: output/grads are zero by convention
        p = jnp.where(row_valid[None, :, None], p, 0.0)
    gf = g.astype(jnp.float32)
    if delta is None:
        delta = jnp.sum(gf * o.astype(jnp.float32), axis=-1)  # [BH, Sq]
    dv = jnp.einsum("bqk,bqd->bkd", p, gf)
    dp = jnp.einsum("bqd,bkd->bqk", gf, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ------------------------------------------------------------ forward kernel
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, causal: bool,
                scale: float, seq_k: int, causal_offset: int):
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, D]
    block_q, d = q.shape
    q_idx = pl.program_id(1)
    q_offset = q_idx * block_q + causal_offset

    num_kb = seq_k // block_k

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k_tile = k_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_tile = v_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        valid = None
        if causal:
            rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            valid = rows >= cols
            s = jnp.where(valid, s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        if valid is not None:
            # explicit zero: a fully-masked row has m_new == NEG_INF and would
            # otherwise get p == 1 at masked positions
            p = jnp.where(valid, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_tile, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    if causal:
        # last k tile that any row of this q block can see
        hi = jnp.minimum(
            num_kb, (q_offset + block_q - 1) // block_k + 1
        ).astype(jnp.int32)
        hi = jnp.maximum(hi, 0)
    else:
        hi = num_kb
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))  # noqa: E741
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]  # [block_q, 1] lane-broadcastable


def _pallas_fwd(q, k, v, causal: bool, scale: float, block_q: int, block_k: int,
                interpret: bool, kv_rep: int = 1):
    """q: [BH, S, D], k/v: [BHk, S, D] with BH == BHk*kv_rep → (o, lse[f32]).

    GQA is handled in the BlockSpec index map (q batch b reads k/v batch
    b // kv_rep) — K/V are never materialized at full head count."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale, seq_k=sk,
        causal_offset=sk - sq,
    )
    out, lse3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i, r=kv_rep: (b // r, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i, r=kv_rep: (b // r, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse3[..., 0]


# ------------------------------------------------------------ backward: dQ
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_k: int, causal: bool, scale: float, seq_k: int, causal_offset: int):
    q = q_ref[0].astype(jnp.float32)  # [block_q, D]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]  # [block_q, 1] — broadcasts over the lane (k) dim
    delta = delta_ref[0]
    block_q, d = q.shape
    q_idx = pl.program_id(1)
    q_offset = q_idx * block_q + causal_offset
    num_kb = seq_k // block_k

    def body(kb, dq_acc):
        k_tile = k_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_tile = v_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        valid = None
        if causal:
            rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            valid = rows >= cols
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_k]
        if valid is not None:
            # fully-masked rows carry a sentinel lse; zero p explicitly
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(
            do, v_tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        return dq_acc + jax.lax.dot_general(
            ds, k_tile, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        hi = jnp.maximum(jnp.minimum(num_kb, (q_offset + block_q - 1) // block_k + 1), 0)
    else:
        hi = num_kb
    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


# ----------------------------------------------------------- backward: dK/dV
def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *,
                block_q: int, causal: bool, scale: float, seq_q: int, causal_offset: int):
    k = k_ref[0].astype(jnp.float32)  # [block_k, D]
    v = v_ref[0].astype(jnp.float32)
    block_k, d = k.shape
    k_idx = pl.program_id(1)
    k_offset = k_idx * block_k
    num_qb = seq_q // block_q

    def body(qb, carry):
        dk_acc, dv_acc = carry
        q_tile = q_ref[0, pl.dslice(qb * block_q, block_q), :].astype(jnp.float32)
        do_tile = do_ref[0, pl.dslice(qb * block_q, block_q), :].astype(jnp.float32)
        lse_tile = lse_ref[0, pl.dslice(qb * block_q, block_q), :]   # [block_q, 1]
        delta_tile = delta_ref[0, pl.dslice(qb * block_q, block_q), :]
        s = jax.lax.dot_general(
            q_tile, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        valid = None
        if causal:
            rows = qb * block_q + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_offset + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            valid = rows >= cols
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse_tile)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dv_acc = dv_acc + jax.lax.dot_general(
            p, do_tile, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # pᵀ·dO : [block_k, D]
        dp = jax.lax.dot_general(
            do_tile, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_tile)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q_tile, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # dSᵀ·Q : [block_k, D]
        return dk_acc, dv_acc

    if causal:
        # first q tile whose last row can see this k block
        lo = jnp.maximum(jnp.minimum((k_offset - causal_offset) // block_q, num_qb), 0)
    else:
        lo = 0
    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, num_qb, body, (z, z))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, o, lse, g, causal: bool, scale: float,
                block_q: int, block_k: int, interpret: bool, kv_rep: int = 1,
                delta=None):
    bh, sq, d = q.shape
    bhk, sk, _ = k.shape
    off = sk - sq
    if delta is None:
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BH, Sq]
    lse3 = lse[..., None]      # trailing singleton lane dim for TPU tiling
    delta3 = delta[..., None]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal, scale=scale,
                          seq_k=sk, causal_offset=off),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # q
            pl.BlockSpec((1, sk, d), lambda b, i, r=kv_rep: (b // r, 0, 0)),   # k
            pl.BlockSpec((1, sk, d), lambda b, i, r=kv_rep: (b // r, 0, 0)),   # v
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # do
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),   # lse
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),   # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, g, lse3, delta3)

    # dK/dV at query-head granularity (fp32 when reducing over a GQA group),
    # then segment-summed back to kv heads — inputs stay unrepeated.
    acc_dt = jnp.float32 if kv_rep > 1 else k.dtype
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, causal=causal, scale=scale,
                          seq_q=sq, causal_offset=off),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, r=kv_rep: (b // r, j, 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, j, r=kv_rep: (b // r, j, 0)),  # v
            pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),        # q
            pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),        # do
            pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0)),        # lse
            pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0)),        # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), acc_dt),
            jax.ShapeDtypeStruct((bh, sk, d), acc_dt),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(k, v, q, g, lse3, delta3)
    if kv_rep > 1:
        dk = dk.reshape(bhk, kv_rep, sk, d).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bhk, kv_rep, sk, d).sum(axis=1).astype(v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------- vjp wiring
def kernel_shapes_ok(sq: int, sk: int) -> bool:
    """The kernels tile the sequence in sublane multiples."""
    return sq % 8 == 0 and sk % 8 == 0


def _use_kernel(sq: int, sk: int, interpret: bool) -> bool:
    """The platform chooses: the kernel on the chip (and in interpret mode),
    the jnp reference on the CPU. A shape the kernel cannot tile is an error
    there, never a quiet switch to the reference."""
    if not (interpret or on_tpu()):
        return False
    if not kernel_shapes_ok(sq, sk):
        raise ValueError(
            f"flash attention kernel needs sequence lengths that are "
            f"multiples of 8, got seq_q={sq} seq_k={sk}; pad the batch")
    return True


def _rep_kv(x, rep):
    """[BHk, S, D] → [BHk*rep, S, D] with j → j // rep (jnp fallback only)."""
    return jnp.repeat(x, rep, axis=0)


def block_fwd(qb, kb, vb, causal, scale, kv_rep=1, interpret=False):
    """One attention block: qb [BH, Sq, D], kb/vb [BHk, Sk, D] → (o, lse f32).

    The single dispatch point (kernel vs jnp reference, GQA handling) shared
    by the flash custom_vjp and ring attention's per-ring-step body."""
    sq, sk = qb.shape[1], kb.shape[1]
    if _use_kernel(sq, sk, interpret):
        bq, bk = get_flash_blocks("fwd", sq, sk, qb.shape[-1])
        return _pallas_fwd(qb, kb, vb, causal, scale, bq, bk, interpret,
                           kv_rep=kv_rep)
    kr = _rep_kv(kb, kv_rep) if kv_rep > 1 else kb
    vr = _rep_kv(vb, kv_rep) if kv_rep > 1 else vb
    return _ref_fwd_impl(qb, kr, vr, causal, scale)


def block_bwd(qb, kb, vb, o, lse, g, causal, scale, kv_rep=1, interpret=False,
              delta=None):
    """Backward of one attention block → (dq [BH], dk [BHk], dv [BHk]).
    ``delta`` (rowsum(g∘o)) may be precomputed by callers that reuse it
    across blocks (ring attention)."""
    sq, sk = qb.shape[1], kb.shape[1]
    if _use_kernel(sq, sk, interpret):
        bq, bk = get_flash_blocks("bwd", sq, sk, qb.shape[-1])
        return _pallas_bwd(qb, kb, vb, o, lse, g, causal, scale, bq, bk,
                           interpret, kv_rep=kv_rep, delta=delta)
    if kv_rep > 1:
        bhk, _, d = kb.shape
        dq, dkr, dvr = _ref_bwd_impl(qb, _rep_kv(kb, kv_rep), _rep_kv(vb, kv_rep),
                                     o, lse, g, causal, scale, delta=delta)
        dk = dkr.reshape(bhk, kv_rep, sk, d).sum(axis=1).astype(kb.dtype)
        dv = dvr.reshape(bhk, kv_rep, sk, d).sum(axis=1).astype(vb.dtype)
        return dq, dk, dv
    return _ref_bwd_impl(qb, kb, vb, o, lse, g, causal, scale, delta=delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, causal, scale, interpret, kv_rep=1):
    out, _ = _flash_core_fwd(q, k, v, causal, scale, interpret, kv_rep)
    return out


def _flash_core_fwd(q, k, v, causal, scale, interpret, kv_rep=1):
    out, lse = block_fwd(q, k, v, causal, scale, kv_rep, interpret)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, scale, interpret, kv_rep, res, g):
    q, k, v, o, lse = res
    return block_bwd(q, k, v, o, lse, g, causal, scale, kv_rep, interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_fwd(q, k, v, *, causal: bool = False, scale: float | None = None,
                        interpret: bool = False):
    """Public entry: q,k,v [B, S, H, D] (paddle layout) → [B, S, H, D].

    GQA (fewer KV heads than query heads) is handled inside the kernel via
    index maps — K/V are never repeated to full head count."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    rep = h // hk if hk != h else 1
    qb = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kb = jnp.moveaxis(k, 2, 1).reshape(b * hk, sk, d)
    vb = jnp.moveaxis(v, 2, 1).reshape(b * hk, sk, d)
    ob = _flash_core(qb, kb, vb, causal, scale, interpret, rep)
    return jnp.moveaxis(ob.reshape(b, h, sq, d), 1, 2)


def flash_attention_on_mesh(q, k, v, *, mesh, batch_axes, head_axis,
                            causal: bool = False):
    """The kernel inside a GSPMD program. Mosaic kernels cannot be
    partitioned automatically, and attention needs no communication across
    batch rows or heads, so each device runs the kernel on its own
    [B/dp, S, H/mp, D] shard inside a shard_map. ``batch_axes`` (a tuple of
    mesh axis names, or ()) shard the batch and ``head_axis`` (or None) the
    heads; K/V heads that the head axis does not divide are repeated first."""
    from jax.sharding import PartitionSpec

    from ...distributed.shard_map_compat import shard_map_compat

    h, hk = q.shape[2], k.shape[2]
    if head_axis is not None and hk % mesh.shape[head_axis]:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    spec = PartitionSpec(tuple(batch_axes) or None, None, head_axis, None)
    fn = functools.partial(flash_attention_fwd, causal=causal)
    return shard_map_compat(fn, mesh, (spec, spec, spec), spec)(q, k, v)
