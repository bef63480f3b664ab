"""Paged decode attention — Pallas TPU kernel for the rows of a serving step
that feed ONE token (``ops/paged_attention.py`` chooses them).

The pool stays where it lies: ``[num_blocks, KV, block_size, D]`` in HBM
(``memory_space=ANY``), one block's KV heads one contiguous piece, so a
context block is ONE asynchronous copy for the keys and one for the values,
straight into VMEM by the row's block table (scalar prefetch). Nothing is
gathered into HBM. The grid is the rows; a row makes as many passes of
``blocks_per_pass`` blocks as ITS length needs and fetches only the blocks
that hold a live position, a row of length 0 makes none. Copies are double
buffered: while a pass is contracted the next one (the row's next, or the
next live row's first) is in flight.

Arithmetic: scores are products of the stored 16-bit values accumulated in
float32 (exact), through one online softmax in float32; the probabilities
meet the values as two 16-bit terms ``p_hi + p_lo`` stacked along the rows
of ONE matmul (16 bits of mantissa: a small-M dot on the MXU is bound by
loading the tiles of its wide operand, and a float32 operand multiplies
those loads; PERF.md section 6, PR 29).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..latent_attention import _NEG

__all__ = ["paged_decode", "BLOCKS_PER_PASS"]

_SUBLANES = 8            # float32 rows of a vreg: query heads are padded to it
BLOCKS_PER_PASS = 8


def _kernel(len_ref, bt_ref, live_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
            per: int, scale: float, window):
    b = pl.program_id(0)
    rows = len_ref.shape[0]
    nb, KV, bs, D = k_hbm.shape
    G = q_ref.shape[2]
    P = bt_ref.shape[0] // rows
    L = per * bs
    n = len_ref[b]

    def first_block(row):
        """The table column a windowed row's walk starts at: the block that holds
        the first key its one query (at ``length - 1``) attends."""
        return jnp.maximum(len_ref[row] - window, 0) // bs

    def blocks(row, j, slot):
        """Pass j of ``row`` into ``slot``: for each of its blocks whether it
        holds a live position, whether the table names a block of the pool,
        and the two copies that bring it.  (Without a window every expression
        is the one the kernel always had: its programs do not move.)"""
        if window is None:
            left = len_ref[row] - j * L
        else:
            col0 = first_block(row) + j * per
            left = len_ref[row] - col0 * bs
        for i in range(per):
            if window is None:
                blk = bt_ref[row * P + j * per + i]
            else:
                blk = bt_ref[row * P + jnp.minimum(col0 + i, P - 1)]
            wanted = i * bs < left
            there = wanted & (blk >= 0) & (blk < nb)
            at = jnp.clip(blk, 0, nb - 1)
            yield i, wanted, there, (
                pltpu.make_async_copy(k_hbm.at[at], kbuf.at[slot, i], sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[at], vbuf.at[slot, i], sems.at[slot, 1]))

    def fetch(row, j, slot):
        for i, wanted, there, copies in blocks(row, j, slot):
            @pl.when(there)
            def _():
                for c in copies:
                    c.start()

            # a hole in the table reads as zeros, as a gather's fill does
            @pl.when(wanted & jnp.logical_not(there))
            def _():
                kbuf[slot, i] = jnp.zeros(kbuf.shape[2:], kbuf.dtype)
                vbuf[slot, i] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(row, j, slot):
        for _, _, there, copies in blocks(row, j, slot):
            @pl.when(there)
            def _():
                for c in copies:
                    c.wait()

    @pl.when(n == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _():
        if window is None:
            trips = (n + L - 1) // L
        else:
            base = first_block(b) * bs      # the first position the walk brings
            trips = (n - base + L - 1) // L
        nxt = live_ref[b + 1]

        # the first live row fetches for itself; blocks a pass does not fetch
        # keep what the buffer held, which must be finite (0 x NaN)
        @pl.when(b == live_ref[0])
        def _():
            kbuf[...] = jnp.zeros_like(kbuf)
            vbuf[...] = jnp.zeros_like(vbuf)
            slot_ref[0] = 0
            fetch(b, 0, 0)

        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def one_pass(j, _):
            slot = slot_ref[0]

            @pl.when(j + 1 < trips)
            def _():
                fetch(b, j + 1, 1 - slot)

            @pl.when((j + 1 == trips) & (nxt < rows))
            def _():
                fetch(nxt, 0, 1 - slot)

            wait(b, j, slot)
            if window is None:
                visible = (j * L + jax.lax.broadcasted_iota(jnp.int32, (G, L), 1)) < n
            else:
                at = base + j * L + jax.lax.broadcasted_iota(jnp.int32, (G, L), 1)
                visible = (at < n) & (at >= n - window)
            for h in range(KV):
                k = kbuf[slot, :, h].reshape(L, D)
                v = vbuf[slot, :, h].reshape(L, D)
                s = jax.lax.dot_general(
                    q_ref[0, h], k, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(visible, s, _NEG)
                m_old = m_ref[h]
                m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m_old - m_new)
                m_ref[h] = m_new
                l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
                hi = p.astype(v.dtype).astype(jnp.float32)
                terms = jnp.concatenate([hi, p - hi], axis=0).astype(v.dtype)
                pv = jax.lax.dot_general(
                    terms, v, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
                acc_ref[h] = acc_ref[h] * corr + pv[:G] + pv[G:]
            slot_ref[0] = 1 - slot
            return 0

        jax.lax.fori_loop(0, trips, one_pass, 0)
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_decode(q, key_cache, value_cache, lengths, block_tables, *,
                 scale: float, blocks_per_pass: int = BLOCKS_PER_PASS,
                 interpret: bool = False, window=None):
    """q [B, KV, g, D] (a row's one token, its query heads by KV head) against
    positions ``[0, lengths[b])`` of row b's context in the pools
    ``[num_blocks, KV, block_size, D]``, found through ``block_tables [B, P]``
    (an entry outside the pool reads as zeros). q and the pools share one
    16-bit float type; ``D`` is whole 128-lane tiles and ``block_size`` whole
    sublane tiles of it. ``g`` need be no sublane tile: the queries are padded
    to one here, never the pool (7 query heads a KV head ride as 8).
    ``window`` (static): row b's token, at ``lengths[b] - 1``, attends the last
    ``window`` positions alone; its fetch list starts at the block that holds
    the first of them, so a row at 16 k under a window of 4,096 brings 65
    blocks and not 256, and the table may name no block behind it.
    Returns [B, KV, g, D] float32, zeros for a row of length 0."""
    B, KV, g, D = q.shape
    nb, _, bs, _ = key_cache.shape
    P = block_tables.shape[1]
    per = max(1, min(blocks_per_pass, P))
    G = -(-g // _SUBLANES) * _SUBLANES
    q = jnp.pad(q, ((0, 0), (0, 0), (0, G - g), (0, 0)))
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, (-P) % per)),
                 constant_values=-1).reshape(-1)
    lengths = lengths.astype(jnp.int32)
    # live[0] the first row with a length, live[b + 1] the next after row b;
    # B where there is none
    ids = jnp.where(lengths > 0, jnp.arange(B, dtype=jnp.int32), B)
    live = jnp.concatenate([jax.lax.cummin(ids, reverse=True),
                            jnp.full((1,), B, jnp.int32)])
    row = lambda b, *_: (b, 0, 0, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, per=per, scale=float(scale),
                          window=None if window is None else int(window)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, KV, G, D), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, KV, G, D), row),
            scratch_shapes=[
                pltpu.VMEM((2, per, KV, bs, D), key_cache.dtype),
                pltpu.VMEM((2, per, KV, bs, D), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), jnp.float32),
        # a row waits for copies the row before it started
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_decode",
        interpret=interpret,
    )(lengths, bt, live, q, key_cache, value_cache)
    return out[:, :, :g]
