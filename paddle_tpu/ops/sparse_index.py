"""The lightning indexer of learned sparse attention (DeepSeek-V3.2-Exp,
``model_type`` ``deepseek_v32``; DeepSeek-AI, "DeepSeek-V3.2-Exp: Boosting
Long-Context Efficiency with DeepSeek Sparse Attention", 2025): a small
attention-like scorer with its OWN paged key cache, which says for every
query which ``topk`` context positions the real attention may see.

    I[t, s] = sum_j w[t, j] * ReLU(q[t, j] . k[s])      (s <= t, float32)
    S_t     = the min(topk, t + 1) positions of highest I[t, .], a tie to
              the lower position (``jax.lax.top_k``'s order)

``k`` is one ``[D]`` key a token and layer (no heads), cached in a pool
``[num_blocks, block_size, D]`` under the block table that the latent cache
of ops/latent_attention.py is read by; ``q`` has ``J`` heads, ``w`` a weight
a head.

``sparse_index`` is one serving step of it: write the step's keys, score
every live position of every query through the pool, select.  The scores are
blocked over the context as ``latent_attention``'s are (one-token rows all at
once, chunk rows one at a time, the trip count the longest live context), and
the selection leaves them in the form ``latent_attention`` attends each kind
of row in (its ``Selection``): a one-token row's as POSITIONS ``[B, topk]``,
by ``jax.lax.top_k`` (0.36 ms at ``[24, 16k]`` on a v5e), to be gathered; the
chunk rows' as a MASK over ONE float32 buffer ``[T + max_q_len, L]`` of all
their queries' scores, by ``select_topk`` (0.86 ms at ``[576, 17,408]``, where
``top_k`` takes 9.5: PERF.md section 6, PR 32).

Both are EXACT and pick the same set.  ``select_topk`` finds the ``k``-th
largest score of a row by a bisection on the scores' bits (32
compare-and-count passes: a float32 read as an integer that orders as the
float does); then everything above it is in, and of the scores equal to it
the lowest positions, by a cumulative count.  No approximation, no selection
by block, no window.

Scopes: ``indexer`` > ``index_write``, ``while/body/``{``index_gather``,
``index_scores``}, ``index_topk``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .latent_attention import Selection, write_entries
from .norms import layer_norm  # noqa: F401  the selector's key norm; re-exported

__all__ = ["sparse_index", "index_scores", "select_topk", "layer_norm"]

F32 = jnp.float32


def index_scores(q, w, k):
    """q [..., Q, J, D], w [..., Q, J] float32, k [..., L, D] -> I [..., Q, L]
    float32: the weighted sum over the heads of the ReLU'd dots."""
    s = jnp.einsum("...qjd,...ld->...qjl", q, k, preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=-2)


def _ordered_bits(scores):
    """float32 -> uint32 that orders as the floats do (-0.0 as +0.0)."""
    scores = jnp.where(scores == 0, 0.0, scores)
    b = jax.lax.bitcast_convert_type(scores.astype(F32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _running_count(x):
    """Inclusive cumulative count of a bool [..., L] along L, float32 (exact
    below 2**24): two levels of products with a triangle of ones, so that the
    sum over L is matmuls and no scan of L steps."""
    *lead, L = x.shape
    n = 128
    pad = (-L) % n
    xb = jnp.pad(x, [(0, 0)] * len(lead) + [(0, pad)]).astype(jnp.bfloat16)
    xb = xb.reshape(*lead, -1, n)
    tri = jnp.triu(jnp.ones((n, n), jnp.bfloat16))
    inside = jnp.einsum("...gn,nm->...gm", xb, tri, preferred_element_type=F32)
    tot = inside[..., -1]                                        # [..., G]
    g = tot.shape[-1]
    before = jnp.einsum("...g,gh->...h", tot, jnp.triu(jnp.ones((g, g), F32), 1),
                        precision=jax.lax.Precision.HIGHEST)
    return (inside + before[..., None]).reshape(*lead, -1)[..., :L]


def select_topk(scores, n_visible, k: int):
    """scores [..., L] float32; ``n_visible`` [...]: positions ``< n_visible``
    are the row's context.  -> bool [..., L]: the ``min(k, n_visible)``
    visible positions of highest score, a tie to the lower position; exactly
    the set ``jax.lax.top_k`` picks among the visible ones."""
    L = scores.shape[-1]
    visible = jnp.arange(L, dtype=jnp.int32) < n_visible[..., None]
    keys = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    # the largest value that at least k keys reach: the k-th largest key
    # (0 where fewer than k positions are visible: every one is then above it)
    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))
    above = keys > thr[..., None]
    ties = keys == thr[..., None]
    room = (k - jnp.sum(above, axis=-1, dtype=jnp.int32)).astype(F32)
    return (above | (ties & (_running_count(ties) <= room[..., None]))) & visible


@jax.named_scope("indexer")
def sparse_index(q, w, keys, cache, seq_lens_decoder, seq_lens_this_time,
                 cu_seqlens_q, block_tables, coords, *, topk: int, max_q_len: int,
                 ctx_block: int = 512):
    """One serving step of the indexer.

    q      [T, J, D]: the packed tokens' index queries (rope applied)
    w      [T, J] float32: their head weights, scaled
    keys   [T, D]: the same tokens' index keys (normed, rope applied)
    cache  [NB, bs, D]: the layer's ``index_k`` pool
    coords ``latent_attention.token_coords`` of the step (row, position,
           live) of each packed token: the latent write's, computed once
    the four after ``cache`` as ``latent_attention`` takes them.

    Returns (``latent_attention``'s ``Selection``, cache', counts).
    ``counts``, over the live queries whose context exceeds ``topk``:
    ``dsa_queries``, ``dsa_positions_scored`` (the context positions scored
    for them) and ``dsa_positions_selected`` (counted in what is returned:
    the positions with ``ok``, the mask's bits)."""
    T, J, D = q.shape
    nb, bs, _ = cache.shape
    B, P = block_tables.shape
    dec, now, cu = seq_lens_decoder, seq_lens_this_time, cu_seqlens_q
    b_idx, abs_pos, valid = coords
    S = int(max_q_len)

    with jax.named_scope("index_write"):
        cache = write_entries(cache, keys, block_tables, b_idx, abs_pos, valid)

    per = max(1, min(P, int(ctx_block) // bs))
    Lc = per * bs
    bt = jnp.pad(block_tables, ((0, 0), (0, (-P) % per)), constant_values=-1)
    bt = jnp.where((bt < 0) | (bt >= nb), nb, bt)                   # -> zeros
    L = bt.shape[1] * bs
    cdt = cache.dtype

    def gather(ids):
        with jax.named_scope("index_gather"):
            g = cache.at[ids].get(mode="fill", fill_value=0)
            return g.reshape(ids.shape[:-1] + (Lc, D)).astype(cdt)

    # ---- rows that feed one token: all B at once --------------------------
    one = now == 1
    n_ctx1 = jnp.where(one, dec + 1, 0)
    first = jnp.clip(cu[:-1], 0, T - 1)
    q1, w1 = q[first].astype(cdt)[:, None], w[first][:, None]      # [B, 1, J, .]

    def one_block(j, out):
        k = gather(jax.lax.dynamic_slice_in_dim(bt, j * per, per, axis=1))
        with jax.named_scope("index_scores"):
            s = index_scores(q1, w1, k)[:, 0]                       # [B, Lc]
        return jax.lax.dynamic_update_slice_in_dim(out, s, j * Lc, axis=1)

    s1 = jax.lax.fori_loop(0, (jnp.max(n_ctx1) + Lc - 1) // Lc, one_block,
                           jnp.zeros((B, L), F32))
    with jax.named_scope("index_topk"):
        k1 = min(int(topk), L)
        seen = jnp.arange(L, dtype=jnp.int32)[None, :] < n_ctx1[:, None]
        _, idx1 = jax.lax.top_k(jnp.where(seen, jnp.where(s1 == 0, 0.0, s1), -jnp.inf), k1)
        ok1 = jnp.arange(k1, dtype=jnp.int32)[None, :] < n_ctx1[:, None]
        sparse1 = n_ctx1 > topk
        counts = {
            "dsa_queries": jnp.sum(sparse1).astype(jnp.int32),
            "dsa_positions_scored": jnp.sum(jnp.where(sparse1, n_ctx1, 0)).astype(jnp.int32),
            "dsa_positions_selected": jnp.sum(ok1 & sparse1[:, None]).astype(jnp.int32)}
    if S == 1:
        return Selection(idx1.astype(jnp.int32), ok1, None), cache, counts

    # ---- rows that feed a chunk: one at a time -----------------------------
    rows = jnp.nonzero(now > 1, size=B, fill_value=0)[0].astype(jnp.int32)
    q_pad = jnp.pad(q, ((0, S), (0, 0), (0, 0))).astype(cdt)
    w_pad = jnp.pad(w, ((0, S), (0, 0)))
    qi = jnp.arange(S, dtype=jnp.int32)

    def chunk_row(i, out):
        r = rows[i]
        start, nq = cu[r], now[r]
        qt = jax.lax.dynamic_slice_in_dim(q_pad, start, S, axis=0)
        wt = jax.lax.dynamic_slice_in_dim(w_pad, start, S, axis=0)
        ids = bt[r]

        def block(j, out):
            k = gather(jax.lax.dynamic_slice_in_dim(ids, j * per, per))
            with jax.named_scope("index_scores"):
                s = index_scores(qt, wt, k)                         # [S, Lc]
            old = jax.lax.dynamic_slice(out, (start, j * Lc), (S, Lc))
            s = jnp.where((qi < nq)[:, None], s, old)
            return jax.lax.dynamic_update_slice(out, s, (start, j * Lc))

        return jax.lax.fori_loop(0, (dec[r] + nq + Lc - 1) // Lc, block, out)

    scores = jax.lax.fori_loop(0, jnp.sum(now > 1).astype(jnp.int32), chunk_row,
                               jnp.zeros((T + S, L), F32))

    with jax.named_scope("index_topk"):
        n_vis = jnp.pad(jnp.where(valid & (now[b_idx] > 1), abs_pos + 1, 0), (0, S))
        mask = select_topk(scores, n_vis, int(topk))
        sparse = n_vis > topk
        counts["dsa_queries"] += jnp.sum(sparse).astype(jnp.int32)
        counts["dsa_positions_scored"] += jnp.sum(jnp.where(sparse, n_vis, 0)).astype(jnp.int32)
        counts["dsa_positions_selected"] += jnp.sum(
            jnp.where(sparse[:, None], mask, False)).astype(jnp.int32)
    return Selection(idx1.astype(jnp.int32), ok1, mask), cache, counts
