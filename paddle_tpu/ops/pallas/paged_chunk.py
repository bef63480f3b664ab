"""Attention of a serving step's CHUNK rows over the paged K/V cache — Pallas
TPU kernel for the rows of ``ops/paged_attention.py`` that feed more than one
token (prompt chunks, the prefill step, speculative drafts), which chooses the
calls that take it.

The pools stay where they lie, ``[num_blocks, KV, block_size, D]`` in HBM
(``memory_space=ANY``): a block's KV heads are ONE asynchronous copy for the
keys and one for the values into VMEM by the row's block table (scalar
prefetch, as ``paged_decode.py`` brings them), double buffered in passes of
``per`` blocks, the next pass (the item's next, or the next item's first) in
flight while one is contracted. Nothing is gathered into HBM, and the scores,
the running max and sum and the probabilities never leave VMEM.

The grid is WORK ITEMS, a compacted list the caller's lengths give
(``latent_rows.work_items``, the latent kernel's own): item -> (row, packed
offset of its first token, that token's position, tokens). A row's ``now`` tokens are cut into tiles of
``tile_tokens`` tokens; a row that feeds one token or none makes no item. An
item walks ITS row's context from the block that holds the first key its FIRST
token attends (block 0 without a window) to the block of its last token, and
fetches no other: the table may name no block behind a window. Queries come
as one copy of the tile at the row's packed offset (the buffer is padded by a
tile, and what lies behind an item's live tokens is attended and dropped);
results go by copies of its live tokens alone (a short tile as its binary
pieces), so every other token of the result keeps what the caller put there
(``input_output_aliases``).

A KV head's ``g`` query heads of the tile's tokens are ONE matrix of
``tokens x g`` rows against that head's ``[L, D]`` keys: the tile is laid so
once an item (``[tokens, H, D]`` as it is copied -> ``[KV, tokens x g, D]``),
not once a pass.

Visible to token t of an item: ``first_key(pos_t, window) <= position <=
pos_t`` (this step's own keys and values are in the pool already, in the value
the XLA pass attends from registers). The arithmetic is the XLA pass's, term
for term (``latent_attention._online``): products of the stored bf16 values
accumulated in float32, one online softmax in float32, the probabilities
rounded to the pool's type before they meet the values, a float32
accumulator, the same floor under the sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_rows import _NEG, work_items

__all__ = ["paged_chunk", "tile_tokens", "padded_heads"]

_PACKING = 16           # rows of a bfloat16 tile: a tile of tokens is whole ones
_ROWS = 512             # query rows (tokens x g) a KV head's products take at most
_MAX_TILE = 64


def tile_tokens(group: int, max_q_len: int) -> int:
    """Tokens of a row a work item attends, from the call's shapes: a power of
    two (a short tile's results go as its binary pieces), whole bfloat16
    sublane tiles, as many as ``_MAX_TILE`` while ``group x tokens`` stays within
    ``_ROWS`` query rows a KV head (64 to g 8, 32 to g 16, then 16), and no more
    than ``max_q_len`` rounded up to such a tile (a draft of 3 rides as 16).
    Every tile of a row walks the row's context again, so the larger tile
    reads less: on the chip 64 tokens beat 32 and 16 at g 4 and at g 7
    (PERF.md section 5, Step 0 of PR 46)."""
    tile = _PACKING
    while tile * 2 <= min(_MAX_TILE, _ROWS // max(int(group), 1)):
        tile *= 2
    while tile // 2 >= max(int(max_q_len), _PACKING):
        tile //= 2
    return tile


def padded_heads(heads: int) -> int:
    """The query heads as the kernel's copies want them: a token's ``[H, D]`` is
    whole tiles of the queries' type and of the float32 result's (28 ride as 32)."""
    return -(-int(heads) // _PACKING) * _PACKING


def _kernel(count_ref, items_ref, bt_ref, q_hbm, k_hbm, v_hbm, _, o_hbm,
            qbuf, qmat, kbuf, vbuf, obuf, sems, qsem, osem, state, m_ref, l_ref, acc_ref, *,
            per: int, P: int, scale: float, TQ: int, g: int, window):
    # (the result comes in as the operand it is aliased to, and goes out)
    i = pl.program_id(0)
    N = items_ref.shape[0] // 4
    nb, KV, bs, D = k_hbm.shape
    M = TQ * g
    L = per * bs
    count = count_ref[0]

    def item(k):
        """(row, packed offset of the first token, its position, tokens)"""
        return items_ref[k], items_ref[N + k], items_ref[2 * N + k], items_ref[3 * N + k]

    def first_column(base):
        """The table column an item's walk starts at: the block that holds the
        first key its FIRST token (at ``base``) attends."""
        return 0 if window is None else jnp.maximum(base - (window - 1), 0) // bs

    def blocks(k, j, slot):
        """Pass j of item k into ``slot``: for each of its blocks whether it
        holds a position the item may see, whether the table names a block of
        the pool, and the two copies that bring it."""
        r, _, base, n = item(k)
        col0 = first_column(base) + j * per
        left = base + n - col0 * bs
        for b in range(per):
            blk = bt_ref[r * P + jnp.minimum(col0 + b, P - 1)]
            wanted = b * bs < left
            there = wanted & (blk >= 0) & (blk < nb)
            at = jnp.clip(blk, 0, nb - 1)
            yield b, wanted, there, (
                pltpu.make_async_copy(k_hbm.at[at], kbuf.at[slot, b], sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[at], vbuf.at[slot, b], sems.at[slot, 1]))

    def fetch(k, j, slot):
        for b, wanted, there, copies in blocks(k, j, slot):
            @pl.when(there)
            def _():
                for c in copies:
                    c.start()

            # a hole in the table reads as zeros, as a gather's fill does
            @pl.when(wanted & jnp.logical_not(there))
            def _():
                kbuf[slot, b] = jnp.zeros(kbuf.shape[2:], kbuf.dtype)
                vbuf[slot, b] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(k, j, slot):
        for _, _, there, copies in blocks(k, j, slot):
            @pl.when(there)
            def _():
                for c in copies:
                    c.wait()

    def queries(k, slot):
        """The copy that brings item k's tile of tokens."""
        return pltpu.make_async_copy(
            q_hbm.at[pl.ds(items_ref[N + k], TQ)], qbuf.at[slot], qsem.at[slot])

    def results(start, n):
        """The copies that take ``n`` tokens of ``obuf`` to the result at
        ``start``: a whole tile one copy, a short one its binary pieces."""
        yield n == TQ, pltpu.make_async_copy(obuf, o_hbm.at[pl.ds(start, TQ)], osem.at[0])
        size = TQ // 2
        while size:
            at = n // (2 * size) * (2 * size)        # the pieces larger than this one
            yield (n < TQ) & ((n & size) != 0), pltpu.make_async_copy(
                obuf.at[pl.ds(at, size)], o_hbm.at[pl.ds(start + at, size)], osem.at[0])
            size //= 2

    def wait_results():
        """Until the copies the item before started have left ``obuf``."""
        for live, copy in results(0, state[2]):
            @pl.when(live)
            def _():
                copy.wait()
        state[2] = 0

    @pl.when(i < count)
    def _():
        _, start, base, n = item(i)
        pos0 = first_column(base) * bs                # the first position the walk brings
        trips = (base + n - pos0 + L - 1) // L

        # the first item fetches for itself; what a pass does not fetch keeps
        # what the buffer held, which must be finite (0 x NaN)
        @pl.when(i == 0)
        def _():
            kbuf[...] = jnp.zeros_like(kbuf)
            vbuf[...] = jnp.zeros_like(vbuf)
            obuf[...] = jnp.zeros_like(obuf)        # the heads that are padding stay so
            state[0] = 0
            state[1] = 0
            state[2] = 0
            queries(0, 0).start()
            fetch(0, 0, 0)

        qslot = state[1]
        queries(i, qslot).wait()
        # a KV head's g query heads of the tile's tokens as ONE matrix, rows
        # (token, head of the group): laid once an item
        for h in range(KV):
            own = qbuf[qslot, :, h * g:(h + 1) * g, :]
            if g % _PACKING:
                own = own.astype(jnp.float32)
            qmat[h] = own.reshape(M, D).astype(qmat.dtype)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # a query row's own position, and the first it attends
        qpos = base + jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) // g

        def one_pass(j, _):
            slot = state[0]

            @pl.when(j + 1 < trips)
            def _():
                fetch(i, j + 1, 1 - slot)

            @pl.when((j + 1 == trips) & (i + 1 < count))
            def _():
                fetch(i + 1, 0, 1 - slot)
                queries(i + 1, 1 - qslot).start()

            wait(i, j, slot)
            kpos = pos0 + j * L + jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
            visible = kpos <= qpos
            if window is not None:
                visible = visible & (kpos > qpos - window)
            for h in range(KV):
                k = kbuf[slot, :, h].reshape(L, D)
                v = vbuf[slot, :, h].reshape(L, D)
                s = jax.lax.dot_general(
                    qmat[h], k, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(visible, s, _NEG)
                m_old = m_ref[h]
                m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m_old - m_new)
                m_ref[h] = m_new
                l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
                acc_ref[h] = acc_ref[h] * corr + pv
            state[0] = 1 - slot
            return 0

        jax.lax.fori_loop(0, trips, one_pass, 0)
        wait_results()
        for h in range(KV):
            o = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            obuf[:, h * g:(h + 1) * g, :] = o.reshape(TQ, g, D)

        for live, copy in results(start, n):
            @pl.when(live)
            def _():
                copy.start()
        state[2] = n
        state[1] = 1 - qslot

        @pl.when(i + 1 == count)
        def _():
            wait_results()


def _vmem_bytes(TQ, H, KV, D, L):
    """What the kernel holds in VMEM, by arithmetic: its buffers (the tile of
    queries twice as it comes and once as matrices, keys and values twice,
    the results, the accumulator, the running max and sum a lane tile wide)
    and a head's pass (scores and their exponentials in float32, the
    probabilities, the product with the values)."""
    Hp = padded_heads(H)
    M = TQ * (H // KV)
    buffers = (2 * TQ * Hp * D * 2 + KV * M * D * 2 + 2 * 2 * L * KV * D * 2
               + TQ * Hp * D * 4 + KV * M * D * 4 + 2 * KV * M * 128 * 4)
    values = 3 * M * L * 4 + M * L * 2 + 2 * M * D * 4
    return buffers + values


@functools.partial(jax.jit, static_argnames=("scale", "max_q_len", "window", "ctx_block",
                                             "interpret"))
def paged_chunk(q, key_cache, value_cache, out, seq_lens_decoder, seq_lens_this_time,
                cu_seqlens_q, block_tables, *, scale: float, max_q_len: int, window=None,
                ctx_block: int = 512, interpret: bool = False):
    """The rows of a step that feed MORE than one token attend their context in
    the pools.

    q [T, H, D] and the pools [num_blocks, KV, block_size, D] share one 16-bit
    float type (``D`` whole 128-lane tiles, ``block_size`` whole sublane tiles
    of it, ``H`` a multiple of ``KV``); the four after ``out`` as
    ``blha_attention`` takes them, this step's keys and values already written.
    ``window`` (static): a token at t attends ``t - window + 1 .. t``, and its
    row's table may name no block behind the first of them.
    ``out`` [>= T, ``padded_heads(H)``, D] float32 is the result: the live
    tokens of those rows are written (heads past ``H``: zeros), every other
    token keeps ``out``'s."""
    T, H, D = q.shape
    Hp = padded_heads(H)
    assert out.shape[1:] == (Hp, D), (out.shape, Hp)
    nb, KV, bs, _ = key_cache.shape
    B, P = block_tables.shape
    g = H // KV
    TQ = tile_tokens(g, max_q_len)
    per = max(1, min(P, int(ctx_block) // bs))
    L = per * bs
    count, items = work_items(seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
                              seq_lens_this_time > 1, tokens=T, tile=TQ)
    N = items.shape[0] // 4
    # a walk that starts at a window's first block may end past the table
    Pp = P + per
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, Pp - P)),
                 constant_values=-1).reshape(-1)
    q = jnp.pad(q, ((0, TQ), (0, Hp - H), (0, 0)))
    rows = out.shape[0]
    if rows < TQ:       # a whole tile's copy names TQ tokens of the result
        out = jnp.pad(out, ((0, TQ - rows), (0, 0), (0, 0)))
    M = TQ * g
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.VMEM((2, TQ, Hp, D), q.dtype),
               pltpu.VMEM((KV, M, D), q.dtype),
               pltpu.VMEM((2, per, KV, bs, D), key_cache.dtype),
               pltpu.VMEM((2, per, KV, bs, D), value_cache.dtype),
               pltpu.VMEM((TQ, Hp, D), out.dtype),
               pltpu.SemaphoreType.DMA((2, 2)),      # passes: a buffer's keys, its values
               pltpu.SemaphoreType.DMA((2,)),        # queries: a buffer each
               pltpu.SemaphoreType.DMA((1,)),
               pltpu.SMEM((3,), jnp.int32),
               pltpu.VMEM((KV, M, 1), jnp.float32),
               pltpu.VMEM((KV, M, 1), jnp.float32),
               pltpu.VMEM((KV, M, D), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_kernel, per=per, P=Pp, scale=float(scale), TQ=TQ, g=g,
                          window=None if window is None else int(window)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N,), in_specs=[any_space] * 4,
            out_specs=any_space, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={6: 0},
        # an item waits for copies the item before it started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 2 * _vmem_bytes(TQ, H, KV, D, L))),
        name="paged_chunk",
        interpret=interpret,
    )(count, items, bt, q, key_cache, value_cache, out)
    return out[:rows]
