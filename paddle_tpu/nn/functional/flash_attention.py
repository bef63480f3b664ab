"""Attention functionals.

Parity: python/paddle/nn/functional/flash_attention.py:198 (flash_attention),
:602 (scaled_dot_product_attention); kernels paddle/phi/kernels/flash_attn_kernel.h.

TPU-native: the public API dispatches to a Pallas flash-attention kernel on
TPU (paddle_tpu.ops.pallas.flash_attention) and to a fused jnp reference
elsewhere (CPU tests, interpret mode). Layout is paddle's [batch, seqlen,
num_heads, head_dim].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ops.dispatch import apply
from ...tensor._helpers import to_tensor_like

__all__ = ["flash_attention", "scaled_dot_product_attention",
           "flash_attn_unpadded", "varlen_attention_core", "sdp_kernel"]


def _ref_attention(q, k, v, *, causal: bool, scale, mask=None, dropout: float = 0.0,
                   dropout_key=None):
    """Reference attention on [B, S, H, D] layout; fp32 softmax accumulator."""
    B, Sq, H, D = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(D)
    if k.shape[2] != H:  # grouped-query attention: repeat kv heads
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qh = jnp.moveaxis(q, 2, 1)  # [B,H,S,D]
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) * sc
    if causal:
        Sk = kh.shape[2]
        cm = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), jnp.zeros((), p.dtype)).astype(p.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return jnp.moveaxis(out, 1, 2)  # back to [B,S,H,D]


def _use_pallas() -> bool:
    """Pallas kernel on the chip, jnp reference on the CPU; ``PADDLE_TPU_ATTN``
    (``ref`` | ``pallas``) overrides for A/B runs."""
    import os

    from ...device import on_tpu

    force = os.environ.get("PADDLE_TPU_ATTN")
    if force == "ref":
        return False
    if force == "pallas":
        return True
    return on_tpu()


def _pallas_attention(q, k, v, causal: bool):
    """The flash kernel, per device: alone on one chip, inside a shard_map
    over the batch (dp, sharding) and head (mp) axes when fleet has built a
    mesh that shards either."""
    from ...distributed.topology import get_hybrid_communicate_group
    from ...ops.pallas.flash_attention import (
        flash_attention_fwd,
        flash_attention_on_mesh,
    )

    hcg = get_hybrid_communicate_group()
    if hcg is not None:
        batch_axes = tuple(a for a in ("dp", "sharding") if hcg.axis_size(a) > 1)
        head_axis = "mp" if hcg.axis_size("mp") > 1 else None
        if batch_axes or head_axis:
            return flash_attention_on_mesh(
                q, k, v, mesh=hcg.mesh, batch_axes=batch_axes,
                head_axis=head_axis, causal=causal)
    return flash_attention_fwd(q, k, v, causal=causal)


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False, *, fixed_seed_offset=None, rng_name="", training=True, name=None):
    """Flash attention on [B, S, H, D]. Returns (out, softmax) like paddle
    (softmax is None unless return_softmax, which the TPU kernel does not
    materialize — documented divergence)."""
    query, key, value = to_tensor_like(query), to_tensor_like(key), to_tensor_like(value)
    drop = float(dropout) if training else 0.0
    drop_key = None
    if drop > 0.0:
        from ...framework.random import default_generator

        drop_key = default_generator().next_key()

    def f(q, k, v):
        if _use_pallas() and drop == 0.0:
            return _pallas_attention(q, k, v, causal)
        return _ref_attention(q, k, v, causal=causal, scale=None, dropout=drop,
                              dropout_key=drop_key)

    out = apply(f, query, key, value, op_name="flash_attention")
    if return_softmax:
        return out, None
    return out, None


def varlen_attention_core(q, k, v, cu_q, cu_k, max_q: int, max_k: int,
                          scale, causal: bool, dropout: float = 0.0,
                          dropout_key=None, padded_layout: bool = False):
    """Variable-length attention over packed token buffers — the TPU-native
    replacement for the reference's varlen flash kernel
    (/root/reference/python/paddle/nn/functional/flash_attention.py:602,
    phi flash_attn_unpadded kernel).

    q [total_q, H, D]; k/v [total_k, KV, D]; cu_q/cu_k [B+1]. Each sequence
    attends only within itself. Implementation: scatter to a padded
    [B, max_len, ...] view, one masked fp32-softmax einsum chain (XLA fuses
    it; r3/r4 measured custom Pallas kernels LOSING to XLA's fused attention
    on v5e), gather back. Static shapes: max_q/max_k
    bound the pad, lengths ride as data, so ragged batches share one
    program. Differentiable end-to-end (packed-sequence training).

    ``padded_layout``: tokens already live at ``b*max_len + i`` (the
    reference's varlen_padded=True contract) — skip the coordinate math.
    """
    total_q, H, D = q.shape
    KV = k.shape[1]
    B = cu_q.shape[0] - 1
    sc = scale if scale is not None else 1.0 / math.sqrt(D)

    def coords(cu, total, max_len):
        tok = jnp.arange(total, dtype=jnp.int32)
        if padded_layout:
            b = tok // max_len
            loc = tok % max_len
            lens = (cu[1:] - cu[:-1]).astype(jnp.int32)
            valid = loc < lens[jnp.clip(b, 0, B - 1)]
            return jnp.clip(b, 0, B - 1), loc, valid
        b = jnp.clip(jnp.searchsorted(cu, tok, side="right") - 1, 0, B - 1)
        loc = tok - cu[b]
        valid = tok < cu[-1]
        return b.astype(jnp.int32), loc.astype(jnp.int32), valid

    bq, lq, vq_m = coords(cu_q, total_q, max_q)
    bk, lk, vk_m = coords(cu_k, k.shape[0], max_k)

    def pad_to(x, b, loc, valid, max_len, nh):
        buf = jnp.zeros((B, max_len, nh, D), x.dtype)
        bs = jnp.where(valid, b, B)
        ls = jnp.where(valid & (loc < max_len), loc, max_len)
        return buf.at[bs, ls].set(x, mode="drop")

    qp = pad_to(q, bq, lq, vq_m, max_q, H)
    kp = pad_to(k, bk, lk, vk_m, max_k, KV)
    vp = pad_to(v, bk, lk, vk_m, max_k, KV)

    len_q = (cu_q[1:] - cu_q[:-1]).astype(jnp.int32)  # [B]
    len_k = (cu_k[1:] - cu_k[:-1]).astype(jnp.int32)
    group = H // KV
    qg = qp.reshape(B, max_q, KV, group, D).astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        kp.astype(jnp.float32)) * sc
    iq = jnp.arange(max_q, dtype=jnp.int32)[None, :]
    jk = jnp.arange(max_k, dtype=jnp.int32)[None, :]
    ok = (jk < len_k[:, None])[:, None, :]  # [B, 1, max_k]
    if causal:
        # bottom-right alignment (flash-attn convention): the last query row
        # lines up with the last key row
        off = (len_k - len_q)[:, None, None]
        ok = ok & (jk[:, None, :] <= iq[:, :, None] + off)
    else:
        ok = jnp.broadcast_to(ok, (B, max_q, max_k))
    logits = jnp.where(ok[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    outp = jnp.einsum("bkgqs,bskd->bqkgd", p, vp.astype(jnp.float32))
    outp = outp.reshape(B, max_q, H, D).astype(q.dtype)
    # gather back to the packed buffer; invalid rows stay zero (the
    # reference's varlen_padded contract: padding is not computed)
    bs = jnp.where(vq_m, bq, B)
    ls = jnp.where(vq_m & (lq < max_q), lq, max_q)
    return outp.at[bs, ls].get(mode="fill", fill_value=0)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """parity: flash_attn_unpadded — varlen attention over packed
    [total_seq_len, num_heads, head_dim] buffers with cu_seqlens. Returns
    (out, softmax-or-None) like the reference (the fused path does not
    materialize softmax; documented divergence shared with
    flash_attention)."""
    query, key, value = (to_tensor_like(t) for t in (query, key, value))
    cu_q = to_tensor_like(cu_seqlens_q)
    cu_k = to_tensor_like(cu_seqlens_k)
    drop = float(dropout) if training else 0.0
    drop_key = None
    if drop > 0.0:
        from ...framework.random import default_generator

        drop_key = default_generator().next_key()

    def f(q, k, v, cq, ck):
        return varlen_attention_core(
            q, k, v, cq.reshape(-1).astype(jnp.int32),
            ck.reshape(-1).astype(jnp.int32), int(max_seqlen_q),
            int(max_seqlen_k), scale, causal, drop, drop_key)

    out = apply(f, query, key, value, cu_q, cu_k, op_name="flash_attn_unpadded")
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, name=None):
    """paddle SDPA parity ([B,S,H,D] layout)."""
    query, key, value = to_tensor_like(query), to_tensor_like(key), to_tensor_like(value)
    drop = float(dropout_p) if training else 0.0
    drop_key = None
    if drop > 0.0:
        from ...framework.random import default_generator

        drop_key = default_generator().next_key()

    if attn_mask is not None:
        attn_mask = to_tensor_like(attn_mask)

        def f(q, k, v, m):
            return _ref_attention(q, k, v, causal=is_causal, scale=None, mask=m,
                                  dropout=drop, dropout_key=drop_key)

        return apply(f, query, key, value, attn_mask, op_name="sdpa")

    def g(q, k, v):
        # SDPA takes any shape (decode steps, odd prompt lengths), so it asks
        # the kernel's shape rule first; F.flash_attention does not and raises
        from ...ops.pallas.flash_attention import kernel_shapes_ok

        if (_use_pallas() and drop == 0.0
                and kernel_shapes_ok(q.shape[1], k.shape[1])):
            return _pallas_attention(q, k, v, is_causal)
        return _ref_attention(q, k, v, causal=is_causal, scale=None, dropout=drop,
                              dropout_key=drop_key)

    return apply(g, query, key, value, op_name="sdpa")


class sdp_kernel:
    """Context manager stub for kernel selection (cuda-flash/mem-efficient/math
    in the reference); TPU has one fused path so this is a no-op switch."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
