"""Flash attention — Pallas TPU kernels, forward AND backward.

Replaces the reference's FlashAttention2 CUDA dependency
(/root/reference/third_party/flashattn, paddle/phi/kernels/flash_attn_kernel.h)
with TPU kernels: online-softmax tiling in VMEM, fp32 accumulators, MXU
matmuls. Layout is paddle's [batch, seq, heads, head_dim].

Forward: one grid cell per (batch*head, q-block); K/V streamed through a
fori_loop of MXU tiles; emits per-row logsumexp (LSE) for the backward. The
scores are TRANSPOSED (K·Qᵀ, keys down the sublanes), so the softmax's max
and sum over keys are elementwise over vregs and m, l, LSE are lane-dense
rows; the output accumulates as [D, block_q] against V given a tile
transposed, and is turned once a q block.

Backward (FlashAttention-2 algorithm): two kernels.
  * dQ:  grid (bh, q-block) — recompute P = exp(S - LSE) tile by tile,
         dS = P * (dO·Vᵀ - Δ), dQ += dS·K, where Δ = rowsum(dO ∘ O).
  * dKV: grid (bh, k-block) — same recomputation streaming Q/dO tiles,
         dV += Pᵀ·dO, dK += dSᵀ·Q, on the transposed scores too, so neither
         product turns a tile.
No S×S matrix is ever materialized; memory is O(S·D) like the forward. LSE
and Δ travel as [BH, S // block_q, block_q]: one lane-dense row a q block
(a trailing axis of 1 is padded to 128 lanes in HBM and in VMEM).

What the MXU is fed follows what the call shows. bf16 q, k and v: their
tiles (and dO's) go to ``dot_general`` as bf16 at ``Precision.DEFAULT`` with
float32 accumulation (the package pins ``highest``, under which a widened tile
runs the several-pass float32 product on bits that were bf16: Q·Kᵀ and dO·Vᵀ
come out bit for bit the same either way); P and dS are rounded to bf16 at
their products, as ``_ref_fwd_impl`` rounds P. Scores, softmax state (m, l,
acc, LSE, Δ), exp and every accumulator are float32, and the scale multiplies
the float32 scores. Any other dtype: float32 operands at the package's
precision, as before.

Causal masking uses FlashAttention-2's bottom-right alignment
(row + seq_k - seq_q >= col) in every path, so kernel and jnp fallback agree
for seq_q != seq_k. Causal loops skip fully-masked tiles via traced loop
bounds. The forward runs its tiles in two loops: those wholly under the
diagonal take no mask, only the ones it crosses apply it (5 % of the kernel
on the chip); the backward kernels, where the mask is one select on a
difference of indices built once, keep one loop (a second cost dkv 4 %).
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


# ------------------------------------------------------- what the MXU is fed
_NT = ((1,), (1,))  # a · bᵀ
_NN = ((1,), (0,))  # a · b


def _operand_dtype(q, k, v):
    """bf16 q, k and v reach the MXU as they arrive; anything else as float32
    (today's float32 callers, and float16, which the MXU does not take)."""
    if q.dtype == k.dtype == v.dtype == jnp.bfloat16:
        return jnp.bfloat16
    return jnp.float32


def _mxu(a, b, contract):
    """One product, accumulated in float32. bf16 operands take the MXU's one
    pass (``DEFAULT``: the package pins ``highest``, which would run several
    on the same bits); float32 operands keep the package's precision."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _tile(ref, index, size, dtype):
    """Rows [index*size, (index+1)*size) of a [1, S, D] block, as an operand."""
    start = pl.multiple_of(index * size, size)
    return ref[0, pl.dslice(start, size), :].astype(dtype)


def _q_minus_k(shape, q_axis):
    """Query index less key index inside a tile whose queries run along
    ``q_axis``. An entry is visible where this is at least the tile's first key
    position less its first query position. Built once a kernel, outside the
    loops; only the tiles the diagonal crosses read it."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))


# ------------------------------------------------------------ forward kernel
def _fwd_kernel(q_ref, k_ref, vt_ref, o_ref, lse_ref, *, causal: bool, scale: float,
                causal_offset: int, dtype):
    """The scores TRANSPOSED, [block_k, block_q]: a query's keys lie down the
    sublanes, so its max and its sum are elementwise over vregs with one short
    fold at the end (along the lanes each is a cross-lane reduction a tile of 8
    rows, and those set the pace once the products are one pass), and m, l and
    lse are ``[1, block_q]`` rows. V comes transposed a tile (``[D, block_k]``)
    so that the output accumulates as ``[D, block_q]`` by a plain product; it
    is turned once, when the block is written."""
    q = q_ref[0].astype(dtype)  # [block_q, D]
    block_q, d = q.shape
    num_kb, _, block_k = vt_ref.shape[1:]
    q_offset = pl.program_id(1) * block_q + causal_offset
    q_minus_k = _q_minus_k((block_k, block_q), 1) if causal else None

    def body(kb, carry, masked):
        acc, m_prev, l_prev = carry
        k_tile = _tile(k_ref, kb, block_k, dtype)
        # the scale goes onto the float32 scores: q is not rounded a second time
        st = _mxu(k_tile, q, _NT) * scale  # [block_k, block_q]
        if masked:
            valid = q_minus_k >= kb * block_k - q_offset
            st = jnp.where(valid, st, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pt = jnp.exp(st - m_new)
        if masked:
            # explicit zero: a fully-masked row has m_new == NEG_INF and would
            # otherwise get p == 1 at masked positions
            pt = jnp.where(valid, pt, 0.0)
        l_new = l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True)
        acc = acc * alpha + _mxu(vt_ref[0, kb].astype(dtype), pt.astype(dtype), _NN)
        return acc, m_new, l_new

    carry = (jnp.zeros((d, block_q), jnp.float32),
             jnp.full((1, block_q), NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32))
    if causal:
        # tiles [0, hi) hold a key some row of this q block can see; the first
        # ``under`` of them hold no key that any row cannot and take no mask
        hi = jnp.clip((q_offset + block_q - 1) // block_k + 1, 0, num_kb)
        under = jnp.clip((q_offset + 1) // block_k, 0, hi)
        carry = jax.lax.fori_loop(
            0, under, functools.partial(body, masked=False), carry)
        acc, m, l = jax.lax.fori_loop(  # noqa: E741
            under, hi, functools.partial(body, masked=True), carry)
    else:
        acc, m, l = jax.lax.fori_loop(  # noqa: E741
            0, num_kb, functools.partial(body, masked=False), carry)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).T.astype(o_ref.dtype)
    lse_ref[0, pl.dslice(pl.program_id(1), 1), :] = m + jnp.log(l_safe)


def _pallas_fwd(q, k, v, causal: bool, scale: float, block_q: int, block_k: int,
                interpret: bool, kv_rep: int = 1):
    """q: [BH, S, D], k/v: [BHk, S, D] with BH == BHk*kv_rep → (o, lse[f32]).

    GQA is handled in the BlockSpec index map (q batch b reads k/v batch
    b // kv_rep) — K/V are never materialized at full head count."""
    bh, sq, d = q.shape
    bhk, sk, _ = k.shape
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, causal_offset=sk - sq,
        dtype=_operand_dtype(q, k, v),
    )
    # V a tile transposed, [BHk, tiles, D, block_k]: the kernel picks a tile by
    # its number and never slices the lanes
    vt = jnp.swapaxes(v.reshape(bhk, sk // block_k, block_k, d), 2, 3)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i, r=kv_rep: (b // r, 0, 0)),
            pl.BlockSpec((1,) + vt.shape[1:], lambda b, i, r=kv_rep: (b // r, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            # a head's lse whole, one [1, block_q] row a q block: it stays in
            # VMEM while the head's q blocks write their rows
            pl.BlockSpec((1, sq // block_q, block_q), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq // block_q, block_q), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, vt)
    return out, lse.reshape(bh, sq)


# ------------------------------------------------------------ backward: dQ
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_k: int, causal: bool, scale: float, seq_k: int,
               causal_offset: int, dtype):
    q = q_ref[0].astype(dtype)  # [block_q, D]
    do = do_ref[0].astype(dtype)
    block_q, d = q.shape
    lse = lse_ref[0, pl.dslice(pl.program_id(1), 1), :].reshape(block_q, 1)
    delta = delta_ref[0, pl.dslice(pl.program_id(1), 1), :].reshape(block_q, 1)
    q_offset = pl.program_id(1) * block_q + causal_offset
    num_kb = seq_k // block_k
    q_minus_k = _q_minus_k((block_q, block_k), 0) if causal else None

    def body(kb, dq_acc):
        k_tile = _tile(k_ref, kb, block_k, dtype)
        v_tile = _tile(v_ref, kb, block_k, dtype)
        s = _mxu(q, k_tile, _NT) * scale
        p = jnp.exp(s - lse)  # [block_q, block_k]
        if causal:
            # a select, not a sentinel in s: fully-masked rows carry a
            # sentinel lse, so exp(s - lse) says nothing there
            p = jnp.where(q_minus_k >= kb * block_k - q_offset, p, 0.0)
        ds = p * (_mxu(do, v_tile, _NT) - delta)
        return dq_acc + _mxu(ds.astype(dtype), k_tile, _NN)

    dq = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        hi = jnp.clip((q_offset + block_q - 1) // block_k + 1, 0, num_kb)
    else:
        hi = num_kb
    dq = jax.lax.fori_loop(0, hi, body, dq)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


# ----------------------------------------------------------- backward: dK/dV
def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *,
                block_q: int, causal: bool, scale: float, seq_q: int,
                causal_offset: int, dtype):
    """The scores TRANSPOSED, [block_k, block_q]: keys on the sublanes, as dK
    and dV have them, so Pᵀ·dO and dSᵀ·Q are plain products with nothing to
    turn, and a query's lse and delta lie along the lanes (``[1, block_q]``
    rows, which broadcast over the keys)."""
    k = k_ref[0].astype(dtype)  # [block_k, D]
    v = v_ref[0].astype(dtype)
    block_k, d = k.shape
    k_offset = pl.program_id(1) * block_k
    num_qb = seq_q // block_q
    q_minus_k = _q_minus_k((block_k, block_q), 1) if causal else None

    def body(qb, carry):
        dk_acc, dv_acc = carry
        q_tile = _tile(q_ref, qb, block_q, dtype)
        do_tile = _tile(do_ref, qb, block_q, dtype)
        st = _mxu(k, q_tile, _NT) * scale  # [block_k, block_q]
        pt = jnp.exp(st - lse_ref[0, pl.dslice(qb, 1), :])
        if causal:
            pt = jnp.where(
                q_minus_k >= k_offset - (qb * block_q + causal_offset), pt, 0.0)
        dv_acc = dv_acc + _mxu(pt.astype(dtype), do_tile, _NN)  # Pᵀ·dO
        dst = pt * (_mxu(v, do_tile, _NT) - delta_ref[0, pl.dslice(qb, 1), :])
        dk_acc = dk_acc + _mxu(dst.astype(dtype), q_tile, _NN)  # dSᵀ·Q
        return dk_acc, dv_acc

    # the first q tile whose last row can see this k block
    lo = jnp.clip((k_offset - causal_offset) // block_q, 0, num_qb) if causal else 0
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
    dtype = _operand_dtype(q, k, v)
    if delta is None:
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BH, Sq]
    # lse and delta as one [1, block_q] row a q block; a head's rows whole in
    # VMEM, each kernel picks its row by the block's number
    rows = (bh, sq // block_q, block_q)
    lse, delta = lse.reshape(rows), delta.reshape(rows)
    row_spec = pl.BlockSpec((1,) + rows[1:], lambda b, i: (b, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal, scale=scale,
                          seq_k=sk, causal_offset=off, dtype=dtype),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # q
            pl.BlockSpec((1, sk, d), lambda b, i, r=kv_rep: (b // r, 0, 0)),   # k
            pl.BlockSpec((1, sk, d), lambda b, i, r=kv_rep: (b // r, 0, 0)),   # v
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # do
            row_spec,                                                # lse
            row_spec,                                                # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    # dK/dV at query-head granularity (fp32 when reducing over a GQA group),
    # then segment-summed back to kv heads — inputs stay unrepeated.
    acc_dt = jnp.float32 if kv_rep > 1 else k.dtype
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, causal=causal, scale=scale,
                          seq_q=sq, causal_offset=off, dtype=dtype),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, r=kv_rep: (b // r, j, 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, j, r=kv_rep: (b // r, j, 0)),  # v
            pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),        # q
            pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),        # do
            row_spec,                                                # lse
            row_spec,                                                # delta
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
    )(k, v, q, g, lse, delta)
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
