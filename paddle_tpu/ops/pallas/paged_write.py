"""Paged cache write — Pallas TPU kernel that puts a serving step's keys and
values into the blocks the rows hold (``ops/paged_attention.py`` chooses the
calls that take it).

The pools stay where they lie, ``[num_blocks, KV, block_size, D]`` in HBM
(``memory_space=ANY``), and ARE the results (``input_output_aliases``). A
token's ``D`` values are one row of a packed 16-bit tile, so a one-row store
is a read-modify-write of the tile around it whoever does it; here it is done
once a PIECE: ``PIECE`` consecutive positions of a row (one sublane tile of
the pool's type) for all KV heads, which one asynchronous copy brings to VMEM
and one puts back. Row ``b``'s run of ``now[b]`` tokens lands at positions
``[dec[b], dec[b] + now[b])``: the pieces that hold one of them are listed
first (scalar work on the lengths and the block table), then brought, given
the run's values by a select and put back in place, several pieces in flight.
A row that feeds nothing, a token past ``cu[-1]`` and a block the table does
not name cost nothing: what the kernel moves follows what is live.

The run's values come from the packed token buffer, kept whole in VMEM with
the token axis on the sublanes as the pool has its slots: a piece's sixteen
tokens are an unaligned window of it, taken as the aligned window around it
rolled by the difference (in float32, which holds a 16-bit float exactly).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_write", "PIECE"]

PIECE = 16          # positions a piece holds: a sublane tile of a 16-bit type
_IN_FLIGHT = 8      # pieces' buffers; half of them are being fetched ahead
_AHEAD = _IN_FLIGHT // 2


def _kernel(dec_ref, now_ref, cu_ref, bt_ref, knew_ref, vnew_ref,
            k_in, v_in, k_out, v_out, kbuf, vbuf, sems,
            p_blk, p_slot, p_tok, p_lo, p_hi):
    B = dec_ref.shape[0]
    P = bt_ref.shape[0] // B
    nb, KV, bs, D = k_in.shape
    total = cu_ref[B]

    # ---- the pieces that hold a live token, in the rows' order --------------
    def row(r, n):
        d = dec_ref[r]
        w = jnp.maximum(jnp.minimum(now_ref[r], total - cu_ref[r]), 0)
        first = d // PIECE
        count = jnp.where(w > 0, (d + w - 1) // PIECE - first + 1, 0)

        def piece(j, n):
            p0 = (first + j) * PIECE
            col = p0 // bs
            blk = bt_ref[r * P + jnp.minimum(col, P - 1)]
            named = (col < P) & (blk >= 0) & (blk < nb)

            @pl.when(named)
            def _():
                p_blk[n] = blk
                p_slot[n] = p0 % bs
                # the token that lands at the piece's first slot, in the
                # buffer's numbering (PIECE rows of padding lead it)
                p_tok[n] = cu_ref[r] + p0 - d + PIECE
                p_lo[n] = jnp.maximum(d - p0, 0)
                p_hi[n] = jnp.minimum(d + w - p0, PIECE)
            return n + named.astype(jnp.int32)

        return jax.lax.fori_loop(0, count, piece, n)

    n = jax.lax.fori_loop(0, B, row, jnp.int32(0))

    def copies(i, slot, into_vmem: bool):
        piece = (p_blk[i], slice(None),
                 pl.ds(pl.multiple_of(p_slot[i], PIECE), PIECE), slice(None))
        for c, (src, dst, buf) in enumerate(((k_in, k_out, kbuf), (v_in, v_out, vbuf))):
            if into_vmem:
                yield pltpu.make_async_copy(src.at[piece], buf.at[slot], sems.at[0, slot, c])
            else:
                yield pltpu.make_async_copy(buf.at[slot], dst.at[piece], sems.at[1, slot, c])

    def start(i, slot, into_vmem):
        for c in copies(i, slot, into_vmem):
            c.start()

    def wait(i, slot, into_vmem):
        for c in copies(i, slot, into_vmem):
            c.wait()

    for i in range(_AHEAD):
        @pl.when(i < n)
        def _():
            start(i, i, True)

    slots = jax.lax.broadcasted_iota(jnp.int32, (1, PIECE, D), 1)

    def one(i, _):
        slot = i % _IN_FLIGHT
        wait(i, slot, True)
        u0 = p_tok[i]
        a = pl.multiple_of((u0 // PIECE) * PIECE, PIECE)
        shift = (2 * PIECE - (u0 - a)) % (2 * PIECE)
        live = (slots >= p_lo[i]) & (slots < p_hi[i])
        for new_ref, buf in ((knew_ref, kbuf), (vnew_ref, vbuf)):
            window = new_ref[:, pl.ds(a, 2 * PIECE), :].astype(jnp.float32)
            run = pltpu.roll(window, shift, 1)[:, :PIECE].astype(buf.dtype)
            buf[slot] = jnp.where(live, run, buf[slot])
        start(i, slot, False)

        # the buffer that piece i + _AHEAD takes was piece i - _AHEAD's
        @pl.when(i >= _AHEAD)
        def _():
            wait(i - _AHEAD, (i - _AHEAD) % _IN_FLIGHT, False)

        @pl.when(i + _AHEAD < n)
        def _():
            start(i + _AHEAD, (i + _AHEAD) % _IN_FLIGHT, True)
        return 0

    jax.lax.fori_loop(0, n, one, 0)

    def drain(i, _):
        wait(i, i % _IN_FLIGHT, False)
        return 0

    jax.lax.fori_loop(jnp.maximum(n - _AHEAD, 0), n, drain, 0)


def paged_write(k_new, v_new, key_cache, value_cache, dec, now, cu, block_tables,
                *, interpret: bool = False):
    """Put this step's keys and values ``k_new``, ``v_new`` [T, KV, D] (packed
    tokens, already of the pools' 16-bit float type) into the pools
    ``[num_blocks, KV, block_size, D]``. Row b's tokens ``cu[b] .. cu[b] +
    now[b]`` (``cu`` the running sum of ``now``, ``cu[-1]`` the tokens in all)
    take positions ``dec[b] ..`` of the blocks ``block_tables[b]`` names; a
    position whose table entry lies outside ``[0, num_blocks)`` or past the
    table's last column is not written. Rows own the blocks they write: no
    two write one block. ``D`` is whole 128-lane tiles and ``block_size``
    whole pieces. Returns the two pools, which are the arguments' buffers
    where the caller donates them."""
    T, KV, D = k_new.shape
    B, P = block_tables.shape
    # a run of w tokens lies in at most (w - 1) // PIECE + 2 pieces
    pieces = T // PIECE + 2 * B
    # the token axis on the sublanes, PIECE rows of padding before it (a
    # run's first piece starts before its first token) and after it
    tail = PIECE + (-T) % PIECE + 2 * PIECE

    def rows_of(x):
        return jnp.pad(jnp.swapaxes(x, 0, 1), ((0, 0), (PIECE, tail), (0, 0)))

    i32 = lambda x: x.astype(jnp.int32)                           # noqa: E731
    smem = lambda: pltpu.SMEM((pieces,), jnp.int32)               # noqa: E731
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    kc, vc = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[whole, whole, anywhere, anywhere],
            out_specs=[anywhere, anywhere],
            scratch_shapes=[
                pltpu.VMEM((_IN_FLIGHT, KV, PIECE, D), key_cache.dtype),
                pltpu.VMEM((_IN_FLIGHT, KV, PIECE, D), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, _IN_FLIGHT, 2)),
                smem(), smem(), smem(), smem(), smem()]),
        out_shape=[jax.ShapeDtypeStruct(key_cache.shape, key_cache.dtype),
                   jax.ShapeDtypeStruct(value_cache.shape, value_cache.dtype)],
        # operands count the four scalar arrays: the pools are 6 and 7
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_write",
        interpret=interpret,
    )(i32(dec), i32(now), i32(cu), i32(block_tables).reshape(-1),
      rows_of(k_new), rows_of(v_new), key_cache, value_cache)
    return kc, vc
