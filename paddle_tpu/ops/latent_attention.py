"""Attention over a paged LATENT cache (multi-head latent attention, MLA:
DeepSeek-V2, arXiv 2405.04434 section 2.1): one cache entry a token and
layer, ``[kv_lora_rank + qk_rope_head_dim]`` values shared by every head, in
a pool ``[num_blocks, block_size, C + R]`` read through per-sequence block
tables like ``ops/paged_attention.py``'s.

The ABSORBED form: a head's query is carried into the latent space before it
meets the cache (``q_lat = q_nope W_uk^T``, done by the caller), so a score is
one dot of ``[q_lat | q_rope]`` with the entry ``[c | rope(k_r)]``, and the
weighted sum of the ``c`` parts goes back through ``W_uv`` afterwards (the
caller again).  Per head and cache byte that is 2 FLOPs for every one of the
H heads, so with 128 heads the decode side sits near the ridge of a v5e
instead of far below it.

Nothing here grows with ``B x H x max_q_len x L``.  A row attends ITS context,
blocked, through an online softmax (float32 running max, sum and
accumulator), in one of two forms that ``rows_in_kernel`` chooses from what
the call shows (the TPU, a bf16 pool and queries, whole tiles):

* the Pallas kernel ``latent_rows`` (ops/pallas/latent_rows.py, scope
  ``rows_kernel``), ONE call for both kinds of row: a row's ``now x H``
  queries in tiles of 16 tokens, a one-token row one tile of ``H`` query rows;
  each brings its own row's blocks by the table straight into VMEM, only those
  that hold a position it may see, and the scores never leave VMEM.  A row at
  rest costs nothing and no row waits for a longer neighbour;
* elsewhere (the CPU, a float32 pool) two XLA loops whose trip count is DATA:
  rows that feed ONE token (decode rows, and a prompt's one-token tail) all
  ``B`` at once, ``[B, H, C + R]`` queries against a ``[B, ctx_block, C + R]``
  gather a pass, for as many passes as the LONGEST of them needs; rows that
  feed a CHUNK (``now > 1``: prompt chunks, speculative drafts) one at a time
  in a loop over the rows that carry one, ``[max_q_len x H, C + R]`` queries
  against ``[ctx_block, C + R]`` of the row's own context, causal inside the
  chunk.

``selection`` (a ``Selection``, made by ops/sparse_index.py) makes the
visible set a query's OWN, in one form for each kind of row, the faster on a
v5e (PERF.md section 6, PR 32).  A row's ONE token attends the K positions
``idx`` names (those with ``ok``): ONE gather of the row's K entries through
the table and a plain softmax over them in place of the blocked pass, in
either form, so what it reads is K a row whatever the context (24 rows at
4-16k, K = 2,048: 1.8 ms against 3.0 for the blocked pass under a mask).  A
chunk row keeps its blocked pass, kernel or loop, and attends ``position <=
its own`` AND ``mask`` (row t: the packed token t's): it still brings every
live entry, what is not selected is masked after the score, and costs what
the dense pass costs (gathered it loses by three).  ``selection_reads``
counts what the two bring, ``rows_taken`` the rows the kernel attended
(for a trunk: ``selection_counts`` and ``latent_counts``).

Scopes (children of ``latent_attention``): ``kv_write``; ``rows_kernel`` (the
kernel's call) or, in the loops, ``kv_gather``, ``scores`` (QK^T, mask, the
online softmax's bookkeeping) and ``values`` (PV); and ``select_gather`` (the
selected entries of the one-token rows, with its own ``scores`` and
``values``)."""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..device import on_tpu
from .pallas.latent_rows import _NEG, latent_rows, tile_tokens

__all__ = ["latent_attention", "latent_counts", "selection_counts", "rope_half", "token_coords",
           "write_entries", "Selection", "selection_reads", "rows_in_kernel", "rows_taken"]

_TABLE_WORDS = 1 << 17   # block-table entries a kernel holds in SMEM (half of it)


def rope_half(x, cos, sin):
    """Rotate-half rope over the last axis. x: [T, ..., R]; cos/sin [T, R/2]
    (already taken at each token's position)."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _online(carry, s, visible, v, pv):
    """One block of the online softmax. carry: (m, l, acc) with m, l [...],
    acc [..., C]; s [..., Lc] float32 scores; visible [..., Lc]; v the block's
    values, which ``pv(p, v)`` contracts with the probabilities."""
    m, l, acc = carry
    with jax.named_scope("scores"):
        s = jnp.where(visible, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(visible, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
    with jax.named_scope("values"):
        acc = acc * corr[..., None] + pv(p.astype(v.dtype), v)
    return m_new, l, acc


def token_coords(T, seq_lens_decoder, seq_lens_this_time, cu_seqlens_q, rows):
    """Of each of ``T`` packed tokens: (row [T], position in its sequence
    [T], whether it is a live token [T])."""
    dec, now, cu = seq_lens_decoder, seq_lens_this_time, cu_seqlens_q
    tok = jnp.arange(T, dtype=jnp.int32)
    b_idx = jnp.clip(
        jnp.searchsorted(cu, tok, side="right").astype(jnp.int32) - 1, 0, rows - 1)
    local = tok - cu[b_idx]
    abs_pos = dec[b_idx] + local
    valid = (tok < cu[-1]) & (local < now[b_idx])
    return b_idx, abs_pos, valid


def write_entries(cache, entries, block_tables, b_idx, abs_pos, valid):
    """``entries`` [T, W] of the live tokens into ``cache`` [NB, bs, W] at
    (block of the token's position by its row's table, slot); a token
    without a block is dropped."""
    nb, bs, _ = cache.shape
    P = block_tables.shape[1]
    blk = block_tables[b_idx, jnp.clip(abs_pos // bs, 0, P - 1)]
    blk = jnp.where(valid & (blk >= 0) & (blk < nb), blk, nb)   # OOB -> drop
    return cache.at[blk, abs_pos % bs].set(entries.astype(cache.dtype), mode="drop")


class Selection(NamedTuple):
    """What each query may attend (module docstring).  ``idx`` [B, K] int32,
    ``ok`` [B, K] bool: the context positions of a row's ONE token, and which
    of the K count.  ``mask`` [T + max_q_len, >= P x bs] bool: row t, a chunk
    row's packed token t over its row's context positions; None where
    ``max_q_len`` is 1 (no row feeds a chunk)."""
    idx: jax.Array
    ok: jax.Array
    mask: Optional[jax.Array]


def _tiling(blocks_per_seq: int, block_size: int, ctx_block: int):
    """(table columns, positions) of the context a blocked pass brings a trip."""
    per = max(1, min(blocks_per_seq, int(ctx_block) // block_size))
    return per, per * block_size


def _trips(n, Lc: int):
    return (n + Lc - 1) // Lc


def rows_in_kernel(q_dtype, cache_dtype, *, heads: int, width: int, rank: int,
                   block_size: int, rows: int, blocks_per_seq: int) -> bool:
    """Whether a call's blocked pass runs in the Pallas kernel
    (``ops/pallas/latent_rows.py``), decided as
    ``ops/paged_attention.decodes_in_kernel`` decides, from what the call
    shows and nothing else: the platform is the TPU; the pool is bfloat16 and
    the queries are of its type; the pool's ``width`` and the ``rank`` of its
    values are whole 128-lane tiles, ``block_size`` and the ``heads`` whole
    sublane tiles; the block table fits the kernel's scalar memory. Anything
    else (the CPU, a float32 pool) keeps the XLA loops."""
    return (on_tpu()
            and jnp.dtype(cache_dtype) == jnp.bfloat16
            and jnp.dtype(q_dtype) == jnp.bfloat16
            and width % 128 == 0 and rank % 128 == 0
            and block_size % 16 == 0 and heads % 16 == 0
            and rows * blocks_per_seq <= _TABLE_WORDS)


def rows_taken(seq_lens_this_time, *, kernel: bool, selected: bool):
    """The rows of one ``latent_attention`` call that attend in the kernel, as
    int32 scalars (one-token rows, chunk rows): with ``kernel``
    (``rows_in_kernel`` of the call) every chunk row, and every one-token row
    unless the call is ``selected`` (a ``selection`` is given: those rows
    gather their selected entries instead)."""
    now = seq_lens_this_time
    return (jnp.sum((now == 1) & (kernel and not selected)).astype(jnp.int32),
            jnp.sum((now > 1) & kernel).astype(jnp.int32))


def selection_reads(seq_lens_decoder, seq_lens_this_time, *, topk: int, gathered: int,
                    block_size: int, blocks_per_seq: int, ctx_block: int = 512,
                    kernel: bool = False, max_q_len: int = 1):
    """Latent entries one ``latent_attention(selection=)`` call with these
    lengths brings for the live queries whose context exceeds ``topk``,
    summed a QUERY (int32 scalar; the arithmetic is the passes'): the token of
    a one-token row its row's ``gathered`` (= K) entries; a chunk row's query
    all of its row's trips, ``ceil((dec + now) / Lc) x Lc``, which the row's
    queries share. With ``kernel`` (``rows_in_kernel`` of the call, whose
    ``max_q_len`` sets the tile) a chunk row's query brings what its TILE of
    the row's tokens brings: the blocks up to the tile's last token,
    ``ceil((dec + tile's end) / block_size) x block_size``."""
    dec, now = seq_lens_decoder, seq_lens_this_time
    _, Lc = _tiling(blocks_per_seq, block_size, ctx_block)
    ones = gathered * jnp.sum((now == 1) & (dec + 1 > topk))
    if kernel:
        tile = tile_tokens(max_q_len)
        u = jnp.arange(int(max_q_len), dtype=jnp.int32)[None, :]     # a row's tokens
        end = jnp.minimum(now[:, None], (u // tile + 1) * tile)
        counted = (now[:, None] > 1) & (u < now[:, None]) & (dec[:, None] + u >= topk)
        chunks = jnp.sum(jnp.where(
            counted, _trips(dec[:, None] + end, block_size) * block_size, 0))
        return (ones + chunks).astype(jnp.int32)
    sparse = jnp.clip(dec + now - topk, 0, now)         # queries at positions >= topk
    chunks = jnp.sum(jnp.where(now > 1, sparse * _trips(dec + now, Lc) * Lc, 0))
    return (ones + chunks).astype(jnp.int32)


def _rows(q_dtype, pool, bt, heads: int, rank: int) -> bool:
    """``rows_in_kernel`` asked with what the POOL says of itself: the ONE
    place the question is put, for the call and for the two counts below."""
    return rows_in_kernel(q_dtype, pool.dtype, heads=heads, width=pool.shape[-1], rank=rank,
                          block_size=pool.shape[-2], rows=bt.shape[0],
                          blocks_per_seq=bt.shape[1])


def latent_counts(q_dtype, pool, now, bt, *, heads: int, rank: int, selected: bool = False):
    """For a trunk's ``counts``: the rows of ONE layer's ``latent_attention`` call
    that attended in the kernel (``rows_taken``), the kernel asked as the call asks
    it.  ``pool``: a layer's; ``selected``: whether the call is given a ``selection``."""
    ones, chunks = rows_taken(now, selected=selected,
                              kernel=_rows(q_dtype, pool, bt, heads, rank))
    return {"latent_rows_kernel": ones, "latent_chunks_kernel": chunks}


def selection_counts(q_dtype, pool, dec, now, bt, selection: Selection, *, heads: int,
                     rank: int, topk: int, max_q_len: int):
    """What ONE layer's ``latent_attention(selection=)`` call brought for its sparse
    queries (``selection_reads``), asked likewise.  (Not in ``latent_counts``: a trunk
    counts its rows before its layers, a selection once its first layer has made one.)"""
    return {"dsa_positions_read": selection_reads(
        dec, now, topk=topk, gathered=selection.idx.shape[1], block_size=pool.shape[-2],
        blocks_per_seq=bt.shape[1], kernel=_rows(q_dtype, pool, bt, heads, rank),
        max_q_len=max_q_len)}


@jax.named_scope("latent_attention")
def latent_attention(q, entries, cache, seq_lens_decoder, seq_lens_this_time,
                     cu_seqlens_q, block_tables, *, rank: int, max_q_len: int,
                     scale: float, ctx_block: int = 512,
                     selection: Optional[Selection] = None):
    """One serving attention step over the latent cache.

    q        [T, H, C + R]: ``[q_lat | rope(q_rope)]`` of the packed tokens
    entries  [T, C + R]:    ``[c | rope(k_r)]`` of the same tokens
    cache    [NB, bs, C + R]
    seq_lens_decoder / seq_lens_this_time / cu_seqlens_q / block_tables: as
    ``blha_attention`` takes them (tokens already cached, tokens this step,
    packed offsets, [B, P] block ids with -1 unassigned).
    ``rank`` = C; ``max_q_len`` (static) bounds a row's tokens this step.
    ``selection``: what each query may attend (``Selection``), or None: every
    position up to the query's own.

    Returns (o_lat [T, H, C] in q's dtype, cache')."""
    T, H, W = q.shape
    C = int(rank)
    nb, bs, _ = cache.shape
    B, P = block_tables.shape
    dec, now, cu = seq_lens_decoder, seq_lens_this_time, cu_seqlens_q

    # ---- token coordinates (as blha_attention) ---------------------------
    b_idx, abs_pos, valid = token_coords(T, dec, now, cu, B)

    with jax.named_scope("kv_write"):
        cache = write_entries(cache, entries, block_tables, b_idx, abs_pos, valid)

    # context is read ``per`` table columns (= ctx_block positions) a pass
    per, Lc = _tiling(P, bs, ctx_block)
    pad = (-P) % per
    bt = jnp.pad(block_tables, ((0, 0), (0, pad)), constant_values=-1)
    bt = jnp.where((bt < 0) | (bt >= nb), nb, bt)                   # -> zeros
    kpos = jnp.arange(Lc, dtype=jnp.int32)
    cdt = cache.dtype

    def gather(ids):
        with jax.named_scope("kv_gather"):
            g = cache.at[ids].get(mode="fill", fill_value=0)
            return g.reshape(ids.shape[:-1] + (Lc, W)).astype(cdt)

    one = now == 1

    def _rows_kernel(o1, take):
        """The rows ``take`` names through the kernel, beside the one-token
        rows' ``o1`` where another form attended them."""
        out = jnp.zeros((T, H, C), q.dtype)
        if o1 is not None:
            out = out.at[jnp.where(one, jnp.clip(cu[:-1], 0, T - 1), T)].set(o1, mode="drop")
        with jax.named_scope("rows_kernel"):
            return latent_rows(q, cache, out, dec, now, cu, block_tables, take,
                               None if selection is None else selection.mask, rank=C,
                               scale=float(scale), max_q_len=int(max_q_len),
                               ctx_block=int(ctx_block))

    # ---- rows that feed one token: all B at once --------------------------
    n_ctx1 = jnp.where(one, dec + 1, 0)
    q1 = q[jnp.clip(cu[:-1], 0, T - 1)].astype(cdt)                 # [B, H, W]

    def one_block(j, carry):
        kv = gather(jax.lax.dynamic_slice_in_dim(bt, j * per, per, axis=1))
        with jax.named_scope("scores"):
            s = jnp.einsum("bhw,blw->bhl", q1, kv,
                           preferred_element_type=jnp.float32) * scale
        vis = ((j * Lc + kpos)[None, :] < n_ctx1[:, None])[:, None, :]
        return _online(carry, s, vis, kv[..., :C], pv_one)

    def pv_one(p, v):
        return jnp.einsum("bhl,blc->bhc", p, v, preferred_element_type=jnp.float32)

    # (the operations of a call without a selection stay in the order they had:
    # tests hold such a program's lowered text to the parent commit's)
    def carry1():
        return (jnp.full((B, H), _NEG, jnp.float32), jnp.zeros((B, H), jnp.float32),
                jnp.zeros((B, H, C), jnp.float32))

    kernel = _rows(q.dtype, cache, block_tables, H, C)
    if selection is None and kernel:
        return _rows_kernel(None, now > 0), cache
    if selection is None:
        _, l1, acc1 = jax.lax.fori_loop(0, _trips(jnp.max(n_ctx1), Lc), one_block, carry1())
    else:
        idx, ok = selection.idx, selection.ok
        with jax.named_scope("select_gather"):
            blk = jnp.take_along_axis(bt, idx // bs, axis=1)
            kv = cache.at[blk, idx % bs].get(mode="fill", fill_value=0).astype(cdt)
        with jax.named_scope("scores"):
            s = jnp.einsum("bhw,bkw->bhk", q1, kv, preferred_element_type=jnp.float32) * scale
        _, l1, acc1 = _online(carry1(), s, (ok & one[:, None])[:, None, :], kv[..., :C], pv_one)
    o1 = (acc1 / jnp.maximum(l1, 1e-30)[..., None]).astype(q.dtype)  # [B, H, C]

    S = int(max_q_len)
    if S == 1:
        at = jnp.where(one, jnp.clip(cu[:-1], 0, T - 1), T)
        return jnp.zeros((T, H, C), q.dtype).at[at].set(o1, mode="drop"), cache
    if kernel:
        return _rows_kernel(o1, now > 1), cache

    # ---- rows that feed a chunk: one at a time -----------------------------
    rows = jnp.nonzero(now > 1, size=B, fill_value=0)[0].astype(jnp.int32)
    q_pad = jnp.pad(q, ((0, S), (0, 0), (0, 0))).astype(cdt)
    qi = jnp.arange(S, dtype=jnp.int32)
    if selection is not None:
        mask = jnp.pad(selection.mask,
                       ((0, 0), (0, bt.shape[1] * bs - selection.mask.shape[1])))

    def pv_chunk(p, v):
        return jnp.einsum("qhl,lc->qhc", p, v, preferred_element_type=jnp.float32)

    def chunk_row(i, out):
        r = rows[i]
        start, base, nq = cu[r], dec[r], now[r]
        qt = jax.lax.dynamic_slice_in_dim(q_pad, start, S, axis=0)   # [S, H, W]
        ids = bt[r]

        def block(j, carry):
            kv = gather(jax.lax.dynamic_slice_in_dim(ids, j * per, per))
            with jax.named_scope("scores"):
                s = jnp.einsum("qhw,lw->qhl", qt, kv,
                               preferred_element_type=jnp.float32) * scale
            vis = ((j * Lc + kpos)[None, :] <= (base + qi)[:, None])[:, None, :]
            if selection is not None:
                vis = vis & jax.lax.dynamic_slice(mask, (start, j * Lc), (S, Lc))[:, None, :]
            return _online(carry, s, vis, kv[:, :C], pv_chunk)

        _, l, acc = jax.lax.fori_loop(
            0, _trips(base + nq, Lc), block,
            (jnp.full((S, H), _NEG, jnp.float32), jnp.zeros((S, H), jnp.float32),
             jnp.zeros((S, H, C), jnp.float32)))
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(out.dtype)
        old = jax.lax.dynamic_slice_in_dim(out, start, S, axis=0)
        o = jnp.where((qi < nq)[:, None, None], o, old)
        return jax.lax.dynamic_update_slice_in_dim(out, o, start, axis=0)

    at = jnp.where(one, jnp.clip(cu[:-1], 0, T - 1), T + S)
    out = jnp.zeros((T + S, H, C), q.dtype).at[at].set(o1, mode="drop")
    out = jax.lax.fori_loop(0, jnp.sum(now > 1).astype(jnp.int32), chunk_row, out)
    return out[:T], cache
