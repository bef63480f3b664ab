"""Attention of a serving step's rows over the paged LATENT cache — Pallas TPU
kernel for the blocked pass of ``ops/latent_attention.py``, which chooses the
calls that take it.

The pool stays where it lies, ``[num_blocks, block_size, C + R]`` in HBM
(``memory_space=ANY``): one block is ONE asynchronous copy into VMEM by the
row's block table (scalar prefetch), double buffered in passes of ``per``
blocks, the next pass (the item's next, or the next item's first) in flight
while one is contracted. Nothing is gathered into HBM, and the scores, the
running max and sum and the probabilities never leave VMEM.

The grid is WORK ITEMS, a compacted list the caller's lengths give: item ->
(row, packed offset of its first token, that token's position, tokens). A
row's ``now x H`` queries are cut into tiles of ``TILE_TOKENS`` tokens; a
one-token row is one item of ``H`` query rows. A row that feeds nothing makes
no item, an item makes ``ceil((position of its last token + 1) / L)`` passes
of ITS row's context and fetches only the blocks that hold a position one of
its tokens may see. Queries come and results go by one copy a token at the
row's packed offset, so a short last tile touches its live tokens alone and
the other tokens of the result keep what the caller put there
(``input_output_aliases``).

Visible to token t of an item: ``position <= its own`` (the step's entries
are in the pool already) AND, where a ``mask`` is given, the mask's row of
that token: the mask comes as int32, a pass of it as the whole sublane tiles
around the item's tokens (one copy), and a token's row is read out of them
at its own sublane. The arithmetic is the XLA pass's, term for term
(``latent_attention._online``): products of the stored bf16 values
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

__all__ = ["latent_rows", "TILE_TOKENS", "tile_tokens", "work_items"]

_NEG = -1e30            # a masked score (``ops/latent_attention.py`` takes it from here)
TILE_TOKENS = 16        # tokens of a row a work item attends (x H query rows)
_SUBLANES = 8           # rows of an int32 tile


def tile_tokens(max_q_len: int) -> int:
    return min(TILE_TOKENS, int(max_q_len))


def work_items(seq_lens_decoder, seq_lens_this_time, cu_seqlens_q, take, *, tokens: int,
               tile: int):
    """The work items of a call, compacted: (count [1], items [4 x N]) with
    ``items`` = row | packed offset of the first token | its position | tokens,
    N = ``tokens // tile + rows``: the tiles of ``tile`` tokens of the rows
    ``take`` [B] names, in the rows' order. (A row's live tokens are those
    before ``cu[-1]``, as the cache write has it.)"""
    dec = seq_lens_decoder.astype(jnp.int32)
    now = seq_lens_this_time.astype(jnp.int32)
    cu = cu_seqlens_q.astype(jnp.int32)
    B, T = dec.shape[0], int(tokens)
    N = T // tile + B
    live = jnp.clip(jnp.minimum(now, jnp.minimum(cu[-1], T) - cu[:-1]), 0)
    tiles = jnp.where(take, (live + tile - 1) // tile, 0)
    ends = jnp.cumsum(tiles)
    k = jnp.arange(N, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(ends, k, side="right").astype(jnp.int32), 0, B - 1)
    first = (k - (ends - tiles)[row]) * tile
    start = jnp.clip(cu[row] + first, 0, T - 1)
    n = jnp.clip(live[row] - first, 1, tile)
    count = jnp.minimum(ends[-1], N).astype(jnp.int32)
    return count.reshape(1), jnp.concatenate([row, start, dec[row] + first, n])


def _kernel(count_ref, items_ref, bt_ref, q_hbm, kv_hbm, *rest,
            per: int, P: int, scale: float, rank: int, TQ: int, masked: bool):
    mask_hbm, rest = (rest[0], rest[1:]) if masked else (None, rest)
    # (the result comes in as the operand it is aliased to, and goes out)
    _, o_hbm, qbuf, kvbuf, obuf, sems, osem, state, m_ref, l_ref, acc_ref, *mbuf = rest
    mbuf = mbuf[0] if masked else None
    kv_sems, q_sems, mask_sems = sems.at[0], sems.at[1], sems.at[2]
    i = pl.program_id(0)
    N = items_ref.shape[0] // 4
    nb, bs, W = kv_hbm.shape
    H = q_hbm.shape[1]
    C = rank
    L = per * bs
    count = count_ref[0]

    def item(k):
        """(row, packed offset of the first token, its position, tokens)"""
        return items_ref[k], items_ref[N + k], items_ref[2 * N + k], items_ref[3 * N + k]

    def blocks(k, j, slot):
        """Pass j of item k into ``slot``: for each of its blocks whether it
        holds a position the item may see, whether the table names a block of
        the pool, and the copy that brings it."""
        r, _, base, n = item(k)
        left = base + n - j * L
        for b in range(per):
            blk = bt_ref[r * P + j * per + b]
            wanted = b * bs < left
            there = wanted & (blk >= 0) & (blk < nb)
            yield b, wanted, there, pltpu.make_async_copy(
                kv_hbm.at[jnp.clip(blk, 0, nb - 1)],
                kvbuf.at[slot, pl.ds(b * bs, bs)], kv_sems.at[slot])

    def mask_copy(k, j, slot):
        """Pass j of the mask rows of item k's tokens: the whole sublane tiles
        around them (a token's row is no copy of its own: it starts nowhere)."""
        tile = items_ref[N + k] // _SUBLANES * _SUBLANES
        return pltpu.make_async_copy(
            mask_hbm.at[pl.ds(pl.multiple_of(tile, _SUBLANES), mbuf.shape[1]),
                        pl.ds(pl.multiple_of(j * L, L), L)],
            mbuf.at[slot], mask_sems.at[slot])

    def fetch(k, j, slot):
        for b, wanted, there, copy in blocks(k, j, slot):
            @pl.when(there)
            def _():
                copy.start()

            # a hole in the table reads as zeros, as a gather's fill does
            @pl.when(wanted & jnp.logical_not(there))
            def _():
                kvbuf[slot, pl.ds(b * bs, bs)] = jnp.zeros((bs, W), kvbuf.dtype)
        if masked:
            mask_copy(k, j, slot).start()

    def wait(k, j, slot):
        for _, _, there, copy in blocks(k, j, slot):
            @pl.when(there)
            def _():
                copy.wait()
        if masked:
            mask_copy(k, j, slot).wait()

    def queries(k, slot):
        """The copies that bring item k's tokens, one a token."""
        _, start, _, n = item(k)
        for t in range(TQ):
            yield t < n, pltpu.make_async_copy(
                q_hbm.at[start + t], qbuf.at[slot, t], q_sems.at[slot])

    def fetch_queries(k, slot):
        for live, copy in queries(k, slot):
            @pl.when(live)
            def _():
                copy.start()

    def results(start, n):
        for t in range(TQ):
            yield t < n, pltpu.make_async_copy(obuf.at[t], o_hbm.at[start + t], osem.at[0])

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
        trips = (base + n + L - 1) // L

        # the first item fetches for itself; what a pass or an item does not
        # fetch keeps what the buffer held, which must be finite (0 x NaN)
        @pl.when(i == 0)
        def _():
            kvbuf[...] = jnp.zeros_like(kvbuf)
            qbuf[...] = jnp.zeros_like(qbuf)
            state[0] = 0
            state[1] = 0
            state[2] = 0
            fetch_queries(0, 0)
            fetch(0, 0, 0)

        qslot = state[1]
        for live, copy in queries(i, qslot):
            @pl.when(live)
            def _():
                copy.wait()

        def run(tokens):
            """The item's passes over ``tokens`` of its tile's tokens."""
            M = tokens * H
            m_ref[:M] = jnp.full((M, 1), _NEG, jnp.float32)
            l_ref[:M] = jnp.zeros((M, 1), jnp.float32)
            acc_ref[:M] = jnp.zeros((M, C), jnp.float32)

            def one_pass(j, _):
                slot = state[0]

                @pl.when(j + 1 < trips)
                def _():
                    fetch(i, j + 1, 1 - slot)

                @pl.when((j + 1 == trips) & (i + 1 < count))
                def _():
                    fetch(i + 1, 0, 1 - slot)
                    fetch_queries(i + 1, 1 - qslot)

                wait(i, j, slot)
                kv = kvbuf[slot]
                s = jax.lax.dot_general(
                    qbuf[qslot, :tokens].reshape(M, W), kv, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) * scale
                kpos = j * L + jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
                ps, corrs = [], []
                for t in range(tokens):
                    rows = slice(t * H, (t + 1) * H)
                    visible = kpos <= base + t
                    if masked:
                        visible = visible & (mbuf[slot, pl.ds(start % _SUBLANES + t, 1), :] != 0)
                    st = jnp.where(visible, s[rows], _NEG)
                    m_old = m_ref[rows]
                    m_new = jnp.maximum(m_old, jnp.max(st, axis=-1, keepdims=True))
                    p = jnp.where(visible, jnp.exp(st - m_new), 0.0)
                    corr = jnp.exp(m_old - m_new)
                    m_ref[rows] = m_new
                    l_ref[rows] = l_ref[rows] * corr + jnp.sum(p, axis=-1, keepdims=True)
                    ps.append(p.astype(kv.dtype))
                    corrs.append(corr)
                # ONE product a tile: tiles of fewer rows cost more a row
                # (PERF.md section 6, PR 43)
                pv = jax.lax.dot_general(
                    jnp.concatenate(ps, axis=0), kv[:, :C], (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)
                acc_ref[:M] = acc_ref[:M] * jnp.concatenate(corrs, axis=0) + pv
                state[0] = 1 - slot
                return 0

            jax.lax.fori_loop(0, trips, one_pass, 0)
            wait_results()
            o = acc_ref[:M] / jnp.maximum(l_ref[:M], 1e-30)
            obuf[:tokens] = o.astype(obuf.dtype).reshape(tokens, H, C)

        if TQ == 1:
            run(1)
        else:
            pl.when(n == 1)(lambda: run(1))
            pl.when(n > 1)(lambda: run(TQ))

        for live, copy in results(start, n):
            @pl.when(live)
            def _():
                copy.start()
        state[2] = n
        state[1] = 1 - qslot

        @pl.when(i + 1 == count)
        def _():
            wait_results()


def _vmem_bytes(TQ, H, W, C, L, masked):
    """What the kernel holds in VMEM, by arithmetic: its buffers (queries and
    passes twice, the results once, the accumulator, the running max and sum
    a lane tile wide) and a pass's values (scores and their exponentials in
    float32, the probabilities, the product with the values, the queries as
    one matrix)."""
    M = TQ * H
    buffers = (2 * M * W * 2 + 2 * L * W * 2 + M * C * 2 + M * C * 4 + 2 * M * 128 * 4
               + (2 * (-(-TQ // _SUBLANES) + 1) * _SUBLANES * L * 4 if masked else 0))
    values = 2 * M * L * 4 + M * L * 2 + M * C * 4 + M * W * 2
    return buffers + values


@functools.partial(jax.jit, static_argnames=("rank", "scale", "max_q_len", "ctx_block",
                                             "interpret"))
def latent_rows(q, cache, out, seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
                block_tables, take, mask=None, *, rank: int, scale: float,
                max_q_len: int, ctx_block: int = 512, interpret: bool = False):
    """The rows ``take`` [B] names of a step attend their context in the pool.

    q [T, H, C + R] and cache [num_blocks, block_size, C + R] share one 16-bit
    float type (``C + R`` whole 128-lane tiles, ``block_size`` and ``H`` whole
    sublane tiles of it); the four after ``out`` as ``latent_attention`` takes
    them, the step's entries already written. ``mask`` [rows, columns] bool or
    None: row t, what the packed token t may see of its row's context besides
    ``position <= its own`` (past its last column, and a token past its last
    row, nothing).
    ``out`` [T, H, rank] is the result: the live tokens of the rows taken are
    written, every other token keeps ``out``'s."""
    T, H, W = q.shape
    nb, bs, _ = cache.shape
    B, P = block_tables.shape
    C = int(rank)
    TQ = tile_tokens(max_q_len)
    per = max(1, min(P, int(ctx_block) // bs))
    L = per * bs
    N = T // TQ + B
    mask_rows = -(-TQ // _SUBLANES) * _SUBLANES
    count, items = work_items(seq_lens_decoder, seq_lens_this_time, cu_seqlens_q, take,
                              tokens=T, tile=TQ)

    Pp = P + (-P) % per
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, Pp - P)),
                 constant_values=-1).reshape(-1)

    operands = [q, cache]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)]
    masked = mask is not None
    if masked:
        # as int32 (a row of a 32-bit tile can be read at any sublane), with
        # the columns of the padded table and the rows of a last item's window
        rows, width = T + mask_rows + _SUBLANES, Pp * bs
        operands.append(jnp.pad(mask[:rows, :width].astype(jnp.int32), (
            (0, max(0, rows - mask.shape[0])), (0, max(0, width - mask.shape[1])))))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(out)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    M = TQ * H
    scratch = [pltpu.VMEM((2, TQ, H, W), q.dtype),
               pltpu.VMEM((2, L, W), cache.dtype),
               pltpu.VMEM((TQ, H, C), out.dtype),
               pltpu.SemaphoreType.DMA((3, 2)),      # passes, queries, mask: a buffer each
               pltpu.SemaphoreType.DMA((1,)),
               pltpu.SMEM((3,), jnp.int32),
               pltpu.VMEM((M, 1), jnp.float32),
               pltpu.VMEM((M, 1), jnp.float32),
               pltpu.VMEM((M, C), jnp.float32)]
    if masked:
        scratch.append(pltpu.VMEM((2, mask_rows + _SUBLANES, L), jnp.int32))
    return pl.pallas_call(
        functools.partial(_kernel, per=per, P=Pp, scale=float(scale), rank=C, TQ=TQ,
                          masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N,), in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY), scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={3 + len(operands) - 1: 0},
        # an item waits for copies the item before it started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * _vmem_bytes(TQ, H, W, C, L, masked)),
        name="latent_rows",
        interpret=interpret,
    )(count, items, bt, *operands)
