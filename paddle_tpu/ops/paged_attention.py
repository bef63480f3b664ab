"""Paged-KV attention core — the TPU-native equivalent of the reference's
serving attention kernel (reference:
/root/reference/python/paddle/incubate/nn/functional/block_multihead_attention.py:19,
kernel /root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu).

Design (SURVEY §7.1: kernels collapse onto XLA):
- KV lives in a global pool of fixed-size blocks ``[num_blocks, KV, bs, D]``;
  a per-sequence ``block_tables [B, blocks_per_seq]`` maps logical positions
  to pool blocks — admission/eviction is host-side free-list bookkeeping, so
  sequences of different lengths share one compiled program.
- One step = (scatter this step's K/V into the pool) + (gather each
  sequence's blocks back) + (padded-batch masked attention). Scatter/gather
  are XLA dynamic-(update-)slice/gather ops that tile fine on TPU; attention
  is one fp32-softmax einsum chain the MXU eats. A hand-written Pallas paged
  kernel was deliberately NOT used: r4 measured XLA's einsum decode path at
  610-688 GB/s vs 299-366 for the Pallas small-M-dot kernel.
- Everything is static-shape: the query side is a packed token buffer
  ``[T, ...]`` (mixed prefill+decode chunks), the key side is
  ``blocks_per_seq * block_size`` — both fixed by the serving engine, so
  admitting/retiring sequences never recompiles.

Supports the reference kernel's full surface: MHA/GQA, in-kernel rope
(neox + interleaved), per-sequence encoder/decoder lengths, mixed batches,
pre-caches (prompt-tuning prefix), int8 cache quantization (static +
dynamic), int32 qkv dequant (qkv_out_scale/qkv_bias), shift/smooth + int8
output quantization, additive encoder/decoder masks.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["blha_attention", "paged_gather_kv", "build_padding_metadata",
           "rope_rotate"]


def rope_rotate(x, cos, sin, neox: bool):
    """Shared rope rotation: x [..., H, D]; cos/sin broadcastable to
    [..., H|1, D/2]. neox=True rotates split halves, else interleaved
    even/odd pairs (the reference kernel's two styles). The single source of
    truth for every in-kernel rope site (paged attention,
    fused_multi_transformer)."""
    c = cos.astype(jnp.float32)
    s = sin.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if neox:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    else:
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
                        ).reshape(xf.shape)
    return out.astype(x.dtype)


def _quantize_u8(x, scale, round_ties_away: bool, max_bound: float,
                 min_bound: float):
    """float -> uint8 cache storage: round(x*scale) clipped, biased by 128
    (dequant contract: (u8 - 128) * dequant_scale — the reference's cache
    int8 convention)."""
    v = x.astype(jnp.float32) * scale
    if round_ties_away:
        v = jnp.trunc(v + jnp.where(v >= 0, 0.5, -0.5))
    else:
        v = jnp.round(v)  # ties to even
    v = jnp.clip(v, min_bound, max_bound)
    return (v + 128.0).astype(jnp.uint8)


def paged_gather_kv(cache, block_tables):
    """cache [NB, KV, bs, D] + block_tables [B, P] -> [B, KV, P*bs, D].
    Out-of-range block ids (free slots marked -1) gather zeros."""
    nb = cache.shape[0]
    bt = jnp.where((block_tables < 0) | (block_tables >= nb), nb, block_tables)
    g = cache.at[bt].get(mode="fill", fill_value=0)  # [B, P, KV, bs, D]
    B, P, KV, bs, D = g.shape
    return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(B, KV, P * bs, D)


def build_padding_metadata(seq_lens_this_time):
    """Host-side helper mirroring the reference's get_padding_offset
    (test/legacy_test/test_block_multihead_attention.py:143): returns
    (padding_offsets, cum_offsets, cu_seqlens_q, cu_seqlens_k) as numpy."""
    import numpy as np

    lens = np.asarray(seq_lens_this_time).reshape(-1).astype(np.int64)
    bsz = lens.shape[0]
    max_len = int(lens.max()) if bsz else 0
    cum_offsets = np.zeros(bsz + 1, np.int32)
    cum_offsets[1:] = np.cumsum(max_len - lens)
    cu = np.zeros(bsz + 1, np.int32)
    cu[1:] = np.cumsum(lens)
    token_num = int(lens.sum())
    padding_offsets = np.zeros(token_num, np.int32)
    for i in range(bsz):
        padding_offsets[cu[i]:cu[i + 1]] = cum_offsets[i]
    return padding_offsets, cum_offsets[:-1], cu, cu.copy()


@partial(jax.jit, static_argnames=(
    "num_heads", "kv_num_heads", "head_dim", "block_size", "max_q_len",
    "use_neox_style", "cache_quant", "round_ties_away", "compute_dtype",
    "has_out_quant"))
@jax.named_scope("paged_attention")
def blha_attention(
    qkv,                       # [T, (H+2*KV)*D] float/bf16 (or int32 w/ qkv_out_scale)
    key_cache,                 # [NB, KV, bs, D] (uint8 when cache_quant)
    value_cache,
    seq_lens_encoder,          # [B] int32: >0 while the seq is in prefill
    seq_lens_decoder,          # [B] int32: tokens already in cache
    seq_lens_this_time,        # [B] int32: tokens this step (0 = inactive row)
    cu_seqlens_q,              # [B+1] int32: token-buffer offsets per seq
    block_tables,              # [B, P] int32 (-1 = unassigned)
    *,
    num_heads: int,
    kv_num_heads: int,
    head_dim: int,
    block_size: int,
    max_q_len: int,            # static padded per-seq query length
    use_neox_style: bool = False,
    cache_quant: str = "none",   # none | static | dynamic
    round_ties_away: bool = True,
    compute_dtype=jnp.float32,
    has_out_quant: bool = False,
    qkv_out_scale=None,        # [(H+2KV)*D] f32: dequant int32 qkv
    qkv_bias=None,             # [(H+2KV)*D]
    rope_emb=None,             # [2, Br, Smax, 1, D/2] f32 (cos, sin)
    mask=None,                 # [B, 1|H, max_q_len, Lk] additive (encoder)
    tgt_mask=None,             # [B, 1|H, 1, Lt] additive (decoder rows)
    pre_key_cache=None,        # [B, KV, Pre, D]
    pre_value_cache=None,
    cache_k_quant_scales=None,    # [KV] (static) | [B, KV] (dynamic)
    cache_v_quant_scales=None,
    cache_k_dequant_scales=None,
    cache_v_dequant_scales=None,
    out_shift=None,            # [H*D]
    out_smooth=None,           # [H*D]
    out_scale: float = -1.0,
    quant_max_bound: float = 127.0,
    quant_min_bound: float = -127.0,
):
    """One serving attention step over the paged cache.

    Returns (out [T, H*D], key_cache', value_cache',
             k_quant_scales', v_quant_scales', k_dequant_scales',
             v_dequant_scales') — scale arrays pass through unchanged except
    in dynamic quant mode, where prefill rows refresh them.

    Scopes (children of ``paged_attention``): ``rope``, ``kv_write``,
    ``kv_gather`` (the gather, its dequant and the pre-cache concat),
    ``scores`` (QK^T, masks, softmax), ``values`` (PV and the return to the
    packed buffer); what is under none is unpacking and token coordinates.
    """
    H, KV, D, bs = num_heads, kv_num_heads, head_dim, block_size
    T = qkv.shape[0]
    B = block_tables.shape[0]
    L = block_tables.shape[1] * bs

    # ---- 1. unpack + dequant + bias ------------------------------------
    if qkv_out_scale is not None:
        qkv_f = qkv.astype(jnp.float32) * qkv_out_scale[None, :]
    else:
        qkv_f = qkv.astype(compute_dtype)
    if qkv_bias is not None:
        qkv_f = qkv_f + qkv_bias[None, :].astype(qkv_f.dtype)
    q = qkv_f[:, : H * D].reshape(T, H, D)
    k = qkv_f[:, H * D:(H + KV) * D].reshape(T, KV, D)
    v = qkv_f[:, (H + KV) * D:].reshape(T, KV, D)

    # ---- 2. token coordinates ------------------------------------------
    tok = jnp.arange(T, dtype=jnp.int32)
    total = cu_seqlens_q[-1]
    b_idx = jnp.clip(
        jnp.searchsorted(cu_seqlens_q, tok, side="right").astype(jnp.int32) - 1,
        0, B - 1)
    local = tok - cu_seqlens_q[b_idx]
    ctx = seq_lens_decoder[b_idx]
    abs_pos = ctx + local
    valid = (tok < total) & (local < seq_lens_this_time[b_idx])

    with jax.named_scope("rope"):
        # ---- 3. rope at absolute positions ---------------------------------
        if rope_emb is not None:
            rb = jnp.minimum(b_idx, rope_emb.shape[1] - 1)
            rp = jnp.clip(abs_pos, 0, rope_emb.shape[2] - 1)
            cos_t = rope_emb[0, rb, rp, 0][:, None, :]  # [T, 1, D/2]
            sin_t = rope_emb[1, rb, rp, 0][:, None, :]
            q = rope_rotate(q, cos_t, sin_t, use_neox_style)
            k = rope_rotate(k, cos_t, sin_t, use_neox_style)

    with jax.named_scope("kv_write"):
        # ---- 4. (dynamic quant) refresh per-(seq, head) scales -------------
        if cache_quant == "dynamic":
            # prefill rows recompute absmax over this step's K/V (the reference
            # computes scales during the encoder pass and reuses them in decode)
            k_pad0 = jnp.zeros((B, max_q_len, KV, D), jnp.float32)
            v_pad0 = jnp.zeros((B, max_q_len, KV, D), jnp.float32)
            bs_idx = jnp.where(valid, b_idx, B)
            lc_idx = jnp.where(valid & (local < max_q_len), local, max_q_len)
            k_pad0 = k_pad0.at[bs_idx, lc_idx].set(
                k.astype(jnp.float32), mode="drop")
            v_pad0 = v_pad0.at[bs_idx, lc_idx].set(
                v.astype(jnp.float32), mode="drop")
            k_absmax = jnp.max(jnp.abs(k_pad0), axis=(1, 3))  # [B, KV]
            v_absmax = jnp.max(jnp.abs(v_pad0), axis=(1, 3))
            is_prefill = (seq_lens_encoder > 0)[:, None]
            new_kq = jnp.where(is_prefill, quant_max_bound / jnp.maximum(k_absmax, 1e-6),
                               cache_k_quant_scales)
            new_vq = jnp.where(is_prefill, quant_max_bound / jnp.maximum(v_absmax, 1e-6),
                               cache_v_quant_scales)
            new_kd = jnp.where(is_prefill, jnp.maximum(k_absmax, 1e-6) / quant_max_bound,
                               cache_k_dequant_scales)
            new_vd = jnp.where(is_prefill, jnp.maximum(v_absmax, 1e-6) / quant_max_bound,
                               cache_v_dequant_scales)
            cache_k_quant_scales, cache_v_quant_scales = new_kq, new_vq
            cache_k_dequant_scales, cache_v_dequant_scales = new_kd, new_vd

        # ---- 5. scatter K/V into the block pool ----------------------------
        nb = key_cache.shape[0]
        blk = block_tables[b_idx, jnp.clip(abs_pos // bs, 0, block_tables.shape[1] - 1)]
        blk = jnp.where(valid & (blk >= 0) & (blk < nb), blk, nb)  # OOB -> drop
        slot = abs_pos % bs
        if cache_quant != "none":
            if cache_quant == "static":
                ksc = cache_k_quant_scales[None, :, None]          # [1, KV, 1]
                vsc = cache_v_quant_scales[None, :, None]
            else:
                ksc = cache_k_quant_scales[b_idx][:, :, None]      # [T, KV, 1]
                vsc = cache_v_quant_scales[b_idx][:, :, None]
            k_store = _quantize_u8(k, ksc, round_ties_away, quant_max_bound,
                                   quant_min_bound)
            v_store = _quantize_u8(v, vsc, round_ties_away, quant_max_bound,
                                   quant_min_bound)
        else:
            k_store = k.astype(key_cache.dtype)
            v_store = v.astype(value_cache.dtype)
        key_cache = key_cache.at[blk, :, slot, :].set(k_store, mode="drop")
        value_cache = value_cache.at[blk, :, slot, :].set(v_store, mode="drop")

    with jax.named_scope("kv_gather"):
        # ---- 6. gather each sequence's context back ------------------------
        k_all = paged_gather_kv(key_cache, block_tables)   # [B, KV, L, D]
        v_all = paged_gather_kv(value_cache, block_tables)
        if cache_quant != "none":
            if cache_quant == "static":
                kd = cache_k_dequant_scales[None, :, None, None]
                vd = cache_v_dequant_scales[None, :, None, None]
            else:
                kd = cache_k_dequant_scales[:, :, None, None]
                vd = cache_v_dequant_scales[:, :, None, None]
            k_all = (k_all.astype(jnp.float32) - 128.0) * kd
            v_all = (v_all.astype(jnp.float32) - 128.0) * vd
            # overlay this step's K/V at full precision: the reference kernel
            # attends the fresh tokens unquantized (only the stored cache is
            # int8), which keeps prefill outputs exact
            ov_b = jnp.where(valid, b_idx, B)
            ov_p = jnp.where(valid, abs_pos, L)
            k_all = k_all.at[ov_b, :, ov_p].set(k.astype(k_all.dtype), mode="drop")
            v_all = v_all.at[ov_b, :, ov_p].set(v.astype(v_all.dtype), mode="drop")
        pre_len = 0
        if pre_key_cache is not None:
            pre_len = pre_key_cache.shape[2]
            k_all = jnp.concatenate([pre_key_cache.astype(k_all.dtype), k_all], axis=2)
            v_all = jnp.concatenate([pre_value_cache.astype(v_all.dtype), v_all], axis=2)
        Lf = pre_len + L

    with jax.named_scope("scores"):
        # ---- 7. padded-batch attention -------------------------------------
        S = max_q_len
        bs_idx = jnp.where(valid, b_idx, B)
        lc_idx = jnp.where(valid & (local < S), local, S)
        q_pad = jnp.zeros((B, S, H, D), q.dtype).at[bs_idx, lc_idx].set(
            q, mode="drop")
        group = H // KV
        qg = q_pad.reshape(B, S, KV, group, D).astype(jnp.float32)
        kf = k_all.astype(jnp.float32)
        logits = jnp.einsum("bskgd,bkld->bkgsl", qg, kf) / (D ** 0.5)

        # causal visibility: query at absolute position p sees keys [0, p] of
        # its own context plus the whole pre-cache prefix
        qpos = (seq_lens_decoder[:, None]
                + jnp.arange(S, dtype=jnp.int32)[None, :])  # [B, S] (rows past the real length are masked on output)
        kpos = jnp.arange(Lf, dtype=jnp.int32)[None, None, :] - pre_len  # [1,1,Lf]
        vis = kpos <= qpos[:, :, None]                                   # [B, S, Lf]
        neg = jnp.asarray(-1e30, jnp.float32)
        logits = jnp.where(vis[:, None, None, :, :], logits, neg)

        def _add_mask(lg, m):
            # m: [B, 1|H, Sq, Lm] additive; key axis aligned at column 0 (the
            # pre-cache prefix occupies the first ``pre_len`` columns, matching
            # the reference's create_attn_mask layout)
            m = m.astype(jnp.float32)
            if m.shape[1] == 1:
                m = jnp.broadcast_to(m, (B, H, m.shape[2], m.shape[3]))
            mh = m.reshape(B, KV, group, m.shape[2], m.shape[3])
            Lm, Sq = m.shape[3], m.shape[2]
            if Lm < Lf:
                mh = jnp.pad(mh, ((0, 0),) * 4 + ((0, Lf - Lm),))
            elif Lm > Lf:
                mh = mh[..., :Lf]
            if Sq < S:
                mh = jnp.pad(mh, ((0, 0),) * 3 + ((0, S - Sq), (0, 0)))
            elif Sq > S:
                mh = mh[..., :S, :]
            return lg + mh

        if mask is not None:
            # encoder-phase custom mask applies to prefill rows only
            enc_rows = (seq_lens_encoder > 0)[:, None, None, None, None]
            logits = jnp.where(enc_rows, _add_mask(logits, mask), logits)
        if tgt_mask is not None:
            dec_rows = ((seq_lens_encoder <= 0) &
                        (seq_lens_this_time > 0))[:, None, None, None, None]
            logits = jnp.where(dec_rows, _add_mask(logits, tgt_mask), logits)

        p = jax.nn.softmax(logits, axis=-1)
    with jax.named_scope("values"):
        out_pad = jnp.einsum("bkgsl,bkld->bskgd", p, v_all.astype(jnp.float32))
        out_pad = out_pad.reshape(B, S, H, D)

        # ---- 8. gather back to the packed token buffer -----------------
        out = out_pad.at[bs_idx, lc_idx].get(mode="fill", fill_value=0)  # [T, H, D]
        out = out.reshape(T, H * D)
    # smooth-quant epilogue: (x + shift) * smooth — the reference kernel's
    # order (shift first, then the per-channel smoothing scale)
    if out_shift is not None:
        out = out + out_shift[None, :].astype(out.dtype)
    if out_smooth is not None:
        out = out * out_smooth[None, :].astype(out.dtype)
    if has_out_quant:
        vq = out.astype(jnp.float32) * out_scale * quant_max_bound
        if round_ties_away:
            vq = jnp.trunc(vq + jnp.where(vq >= 0, 0.5, -0.5))
        else:
            vq = jnp.round(vq)
        out = jnp.clip(vq, quant_min_bound, quant_max_bound).astype(jnp.int8)
    else:
        out = out.astype(compute_dtype)
    return (out, key_cache, value_cache,
            cache_k_quant_scales, cache_v_quant_scales,
            cache_k_dequant_scales, cache_v_dequant_scales)
