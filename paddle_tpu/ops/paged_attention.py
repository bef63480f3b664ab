"""Paged-KV attention core — the TPU-native equivalent of the reference's
serving attention kernel (reference:
/root/reference/python/paddle/incubate/nn/functional/block_multihead_attention.py:19,
kernel /root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu).

Design (SURVEY §7.1: kernels collapse onto XLA):
- KV lives in a global pool of fixed-size blocks ``[num_blocks, KV, bs, D]``;
  a per-sequence ``block_tables [B, blocks_per_seq]`` maps logical positions
  to pool blocks — admission/eviction is host-side free-list bookkeeping, so
  sequences of different lengths share one compiled program.
- Heads narrower than a lane tile lie side by side in one: a model whose
  cache spec takes ``lane_packing``'s block keeps ``[num_blocks, KV / pack,
  bs, D * pack]`` (heads of 64: two a row of 128 lanes), and the call reads
  the form off the pool it is handed. The packed buffer's keys ``[T, KV, D]``
  are already such rows ``[T, KV / pack, D * pack]``, and ``pack`` KV heads are
  ONE head of ``D * pack`` to a group of ``pack`` times the query heads whose
  queries are zero outside their own head's lanes: exact zeros in the scores,
  and each query head's own values in its own lanes of the result. So the
  write, both kernels and the XLA pass below all see a pool of whole lane
  tiles and none knows of packing. (A row of 64 lanes is half a tile: the TPU
  compiler gave such a pool a layout of its own and copied each array in and
  out of every program, and neither kernel admits it; PERF.md section 6, PR
  41.) An int8 pool is not packed: its scales are a head's.
- One step = (this step's K/V into the pool) + (attention blocked over the
  context). Nothing grows with ``B x max_q_len x blocks_per_seq x bs``: the
  cost follows what the batch holds, not the table's shape.
  Which rows take which path:
  * rows that feed ONE token (decode rows, a prompt's one-token tail), where
    the call is one ``decodes_in_kernel`` admits (the TPU, an unquantised
    bfloat16 pool and queries of its type, no mask or pre-cache, a pool whose
    rows are whole lane tiles (heads of 128, or of 64 two to a row), blocks
    of whole sublane tiles): the Pallas kernel
    ``ops/pallas/paged_decode.py``. It takes the pools where they lie in
    HBM, brings a row's own context blocks to VMEM by the block table (one
    asynchronous copy a block: the layout below makes a block's KV heads one
    contiguous piece), double buffered, through one online softmax in
    float32. No copy is gathered into HBM, a row makes the passes ITS length
    needs and fetches no block past its end, and the step's own token is
    read from the pool, where the write has just put it in the very value
    the XLA pass attends from registers;
  * the same rows of any other call (the CPU, a float32 or int8 cache,
    masks, pre-caches): the blocked XLA pass. The cache is read
    ``_CTX_BLOCK`` positions a pass (whole table columns), contracted in the
    type it is stored in with float32 accumulation, through an online
    softmax (float32 running max, sum and accumulator; the bookkeeping is
    ``latent_attention._online``) whose trip count is DATA; the rows are
    ordered by context length and run in tiles of ``_ROW_TILE`` rows, each
    tile as many passes as its longest row needs, so a short row does not
    pay for the longest one and a (tile, context block) pair without a live
    position is never gathered;
  * rows that feed a CHUNK (``now > 1``: prompt chunks, the prefill step,
    speculative drafts), where the call is one ``chunks_in_kernel`` admits
    (what ``decodes_in_kernel`` asks, and the list of work items within the
    kernel's scalar memory): the Pallas kernel ``ops/pallas/paged_chunk.py``,
    ONE call for all of them. A tile of a row's tokens is a work item; a KV
    head's ``g`` query heads of the tile are one matrix of ``g x tokens`` rows
    against that head's keys; an item walks ITS row's blocks by the table,
    from the block that holds its first token's first key (a window's first
    block) to its last token's, this step's own tokens read from the pool as
    ``paged_decode`` reads them; scores never leave VMEM;
  * the same rows of any other call: the XLA pass one row at a time in a loop
    over the rows that carry one, ``[max_q_len, H, D]`` queries against the
    row's own context; rows without a chunk cost nothing.
  On the XLA pass this step's own tokens are attended from registers as one
  trailing block (causal inside a chunk), so the cache is read for
  ``[0, dec)`` only and an int8 cache's fresh tokens stay unquantised, as in
  the reference kernel. An int8 block is gathered as its integers and the
  scales are applied to the products, which is exact.
  ``attention_positions`` counts what a call had to attend, what it read
  for that and the rows the kernel took; a trunk takes them from
  ``paged_counts`` and ``ServingEngine`` adds them up
  (``attn_positions_live`` / ``_read``, ``attn_rows_kernel``,
  ``attn_chunks_kernel``).
- The write, and why ONE layout still stands. Where ``writes_in_kernel``
  admits the call (the TPU, an unquantised bfloat16 pool whose rows are whole
  lane tiles, blocks of whole 16-slot pieces; masks and pre-caches do not matter)
  the keys and values go in through the Pallas kernel
  ``ops/pallas/paged_write.py``: it takes both pools where they lie and
  returns them (aliased, in place), lists the PIECES (16 consecutive
  positions of a row, every KV head: one sublane tile of the pool) that hold
  one of this step's tokens, and for each brings the piece to VMEM by the
  block table, selects the run's values in and puts it back, several pieces
  in flight. A token's ``D`` values are half of a packed sublane pair, so a
  one-row store is a read-modify-write of the tile around it whoever does
  it; the kernel does it once a piece and only for what is live: a row that
  feeds nothing, a token past ``cu[-1]`` and a block the table does not name
  cost nothing, and a run longer than a block is just more pieces. Every
  other call (the CPU, a float32 pool, the int8 caches) keeps the scatter
  over the packed buffer by (block, kv head, slot) with a window of one
  head's ``D`` values, which walks every window of the buffer, live or not
  (63 ns each on a v5e: 516 us a layer at 256 tokens x 16 heads x 2, where
  the kernel takes 8-17 us; PERF.md section 6, PR 30 and PR 31). Both write the pool in its own row-major
  layout, the one the gather reads as rows of ``[num_blocks x KV, bs, D]``
  and ``paged_decode`` copies from: written by (block, slot) with a ``[KV,
  D]`` window, the TPU compiler kept a second, transposed copy of every
  layer's pool in each program (3.2 GB at the benchmark's size; PERF.md
  section 6, PR 27). ``cache_write_counts`` counts the tokens written and
  the pieces moved (``kv_write_tokens`` / ``kv_write_blocks``).
- Why a kernel after all: r4 had measured a Pallas decode kernel at 299-366
  GB/s against 610-688 for XLA's einsum, but over a static ring cache with
  every position live and one query head a dot. Over the paged pool the XLA
  pass must gather each context block into a copy that is written to HBM and
  read back, and a tile rides its longest row: 120-190 GB/s of the bytes that
  are live. The kernel measured 4 times faster than that pass (twelve layers
  3.05 ms against 12.41 at serve1's ragged contexts; PERF.md section 6, PR 29).
- A model whose layers are a loop in its program keeps ONE pool with a
  leading layer axis and names the layer by the loop's counter
  (``blha_attention(layer=)``): the layers' blocks are one run of block
  numbers, so nothing below the table knows of layers.
- Everything is static-shape: the query side is a packed token buffer
  ``[T, ...]`` (mixed prefill+decode chunks), the table ``blocks_per_seq``
  columns — both fixed by the serving engine, so admitting/retiring
  sequences never recompiles.

Supports the reference kernel's full surface: MHA/GQA, in-kernel rope
(neox + interleaved), per-sequence encoder/decoder lengths, mixed batches,
pre-caches (prompt-tuning prefix), int8 cache quantization (static +
dynamic), int32 qkv dequant (qkv_out_scale/qkv_bias), shift/smooth + int8
output quantization, additive encoder/decoder masks.
"""
from __future__ import annotations

from functools import partial
import jax
import jax.numpy as jnp

from ..device import on_tpu
from .latent_attention import _NEG, _TABLE_WORDS, _online
from .pallas.paged_chunk import padded_heads, paged_chunk, tile_tokens
from .pallas.paged_decode import paged_decode
from .pallas.paged_write import PIECE, paged_write

__all__ = ["blha_attention", "paged_counts", "attention_positions", "first_key", "decodes_in_kernel",
           "chunks_in_kernel", "cache_write_counts", "writes_in_kernel", "lane_packing",
           "build_padding_metadata", "rope_rotate"]

_CTX_BLOCK = 512    # cache positions a pass over the context reads
_ROW_TILE = 8       # one-token rows that share a trip count
_WRITE_VALUES = 1 << 21  # new keys (and as many values) ``paged_write`` holds whole in VMEM
_PASS_VALUES = 1 << 22   # keys (and as many values) of a pass ``paged_chunk`` holds, twice, in VMEM


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


def _context_block(block_size: int, blocks_per_seq: int):
    """(table columns, cache positions) that one pass over the context reads."""
    per = max(1, min(blocks_per_seq, _CTX_BLOCK // block_size))
    return per, per * block_size


def _trips(positions, per_pass: int):
    return (positions + per_pass - 1) // per_pass


def lane_packing(kv_heads: int, head_dim: int):
    """How a per-head K/V pool lays heads narrower than a lane tile, decided
    from the two sizes alone: ``(pack, block)``, ``pack`` the KV heads that
    share one 128-lane tile (``128 // head_dim`` where that is whole and
    divides ``kv_heads``, else 1) and ``block(block_size)`` the shape of a
    pool block that follows, ``(KV // pack, block_size, D * pack)``: head
    ``pack * j + p`` of a position lies in lanes ``[p * D, (p + 1) * D)`` of
    row ``j``.  At ``pack`` 1 that is the pool as it always was."""
    pack = 128 // head_dim if 0 < head_dim < 128 and 128 % head_dim == 0 else 1
    pack = pack if kv_heads % pack == 0 else 1
    return pack, lambda block_size: (kv_heads // pack, block_size, head_dim * pack)


def _own_lanes(group: int, pack: int, heads: int):
    """[H, pack] bool: which of the ``pack`` places of a lane tile is query
    head h's own, that of its KV head ``h // group``."""
    place = (jnp.arange(heads, dtype=jnp.int32) // group) % pack
    return place[:, None] == jnp.arange(pack, dtype=jnp.int32)


def _one_token_tiles(dec, now):
    """The rows that feed ONE token, ordered by context length and cut into
    tiles of ``_ROW_TILE`` rows, so that a short row rides with short rows.
    Returns (rows, live, cached), each [tiles, _ROW_TILE]: a row's index,
    whether it is such a row (the tail of the order is not), and the cache
    positions it reads, 0 where not live."""
    B = dec.shape[0]
    n = jnp.where(now == 1, dec, -1)
    order = jnp.argsort(-n).astype(jnp.int32)
    pad = (-B) % _ROW_TILE
    rows = jnp.pad(order, (0, pad)).reshape(-1, _ROW_TILE)
    cached = jnp.pad(n[order], (0, pad), constant_values=-1).reshape(rows.shape)
    return rows, cached >= 0, jnp.maximum(cached, 0)


def decodes_in_kernel(q_dtype, cache_dtype, *, head_dim: int, block_size: int,
                      rows: int, blocks_per_seq: int, plain: bool = True) -> bool:
    """Whether a call's one-token rows attend through the Pallas kernel
    (``ops/pallas/paged_decode.py``), decided from what the call shows and
    nothing else: the platform is the TPU; the cache is an unquantised
    bfloat16 pool (the 16-bit float Mosaic takes: it refuses float16) and the
    queries are of its type; ``plain``: no mask, ``tgt_mask`` or pre-cache;
    ``head_dim``, the width of the POOL's rows (``lane_packing``: a head's,
    or two heads of 64 side by side), is whole 128-lane tiles and
    ``block_size`` whole sublane tiles; the block table fits the kernel's
    scalar memory. Anything else takes the blocked XLA pass."""
    return (on_tpu() and plain
            and jnp.dtype(cache_dtype) == jnp.bfloat16
            and jnp.dtype(q_dtype) == jnp.bfloat16
            and head_dim % 128 == 0 and block_size % 16 == 0
            and rows * blocks_per_seq <= _TABLE_WORDS)


def writes_in_kernel(cache_dtype, *, head_dim: int, block_size: int, rows: int,
                     blocks_per_seq: int, tokens: int, kv_heads: int) -> bool:
    """Whether a call's keys and values go into the pool through the Pallas
    kernel (``ops/pallas/paged_write.py``), decided as ``decodes_in_kernel``
    decides: the platform is the TPU; the cache is an unquantised bfloat16
    pool; ``head_dim`` (of the pool's rows, ``kv_heads`` of them a position)
    is whole 128-lane tiles and ``block_size`` whole sublane tiles; the block
    table fits the kernel's scalar memory and the packed buffer's ``tokens``
    x ``kv_heads`` new keys and values its VMEM.
    Masks and pre-caches are the attention's and do not matter here. Anything
    else (the CPU, a float32 pool, the int8 caches) takes the scatter."""
    return (on_tpu() and jnp.dtype(cache_dtype) == jnp.bfloat16
            and head_dim % 128 == 0 and block_size % PIECE == 0
            and rows * blocks_per_seq <= _TABLE_WORDS
            and tokens * kv_heads * head_dim <= _WRITE_VALUES)


def chunks_in_kernel(q_dtype, cache_dtype, *, head_dim: int, block_size: int, rows: int,
                     blocks_per_seq: int, tokens: int, kv_heads: int,
                     plain: bool = True) -> bool:
    """Whether a call's CHUNK rows (``now > 1``) attend through the Pallas kernel
    (``ops/pallas/paged_chunk.py``), decided as ``decodes_in_kernel`` decides and
    from the same things: the platform is the TPU; the cache is an unquantised
    bfloat16 pool and the queries are of its type; ``plain``; ``head_dim`` (of the
    POOL's rows, ``kv_heads`` of them a position) is whole 128-lane tiles and
    ``block_size`` whole sublane tiles; the block table and the list of work
    items (four words a tile of a row's tokens: at most ``tokens / 16 + rows``)
    fit the kernel's scalar memory, a pass of ``_CTX_BLOCK`` positions its VMEM.
    Anything else takes the blocked XLA pass a row at a time."""
    return (on_tpu() and plain
            and jnp.dtype(cache_dtype) == jnp.bfloat16
            and jnp.dtype(q_dtype) == jnp.bfloat16
            and head_dim % 128 == 0 and block_size % 16 == 0
            and rows * blocks_per_seq + 4 * (tokens // 16 + rows) <= _TABLE_WORDS
            and _CTX_BLOCK * kv_heads * head_dim <= _PASS_VALUES)


def cache_write_counts(seq_lens_decoder, seq_lens_this_time, cu_seqlens_q, *,
                       kernel: bool = False):
    """What one ``blha_attention`` call with these lengths writes into ONE
    cache layer, as int32 scalars (tokens, blocks): ``tokens`` the live tokens
    whose keys and values go into the pool (a row's ``now``, cut at
    ``cu[-1]``); ``blocks`` the block pieces (``PIECE`` consecutive positions
    of a row, every KV head) that the row-wise write brings and puts back for
    them where the table names their blocks, as an engine's rows' do: the
    arithmetic is the kernel's. 0 without ``kernel`` (``writes_in_kernel`` of
    the call): the scatter walks the packed buffer and brings no piece."""
    dec, cu = seq_lens_decoder, cu_seqlens_q
    w = jnp.clip(jnp.minimum(seq_lens_this_time, cu[-1] - cu[:-1]), 0)
    pieces = jnp.where(w > 0, (dec + w - 1) // PIECE - dec // PIECE + 1, 0)
    return (jnp.sum(w).astype(jnp.int32),
            jnp.sum(pieces * kernel).astype(jnp.int32))


def first_key(pos, window):
    """The first position a query at ``pos`` attends under ``window`` (it counts the
    query's own position); 0 with no window."""
    return 0 if window is None else jnp.maximum(pos - (window - 1), 0)


def attention_positions(seq_lens_decoder, seq_lens_this_time, *,
                        block_size: int, blocks_per_seq: int,
                        kernel: bool = False, window=None, chunk_tile=None,
                        max_q_len: int = 1):
    """What one ``blha_attention`` call with these lengths attends and what
    it reads for that, as int32 scalars (live, read, rows_kernel): ``live``
    the context of every row fed, ``dec + now``; ``read`` the cache positions
    brought to the products plus this step's own tokens where they come from
    registers; ``rows_kernel`` the one-token rows that went through the
    kernel. The arithmetic is the loops'. On the XLA pass a tile of one-token
    rows reads its longest row's passes for all of its ``_ROW_TILE`` rows and
    a chunk row its own. With ``kernel`` (``decodes_in_kernel`` of the call) a
    one-token row reads its own context, this step's token with it, rounded
    up to a block: the kernel fetches no block past the row's end.  With
    ``chunk_tile`` (``chunks_in_kernel`` of the call: the tokens of a
    ``paged_chunk`` work item, of rows that feed ``max_q_len`` at most) a
    chunk row reads what its tiles' trips bring: each the blocks from the one
    that holds its first token's first key to its last token's.  Under a
    ``window`` every walk starts at the pass (the kernels: the block) that holds
    the first key its first query attends, so ``read`` falls short of ``live``
    by what the window spared."""
    dec, now = seq_lens_decoder, seq_lens_this_time
    _, Lc = _context_block(block_size, blocks_per_seq)
    live = jnp.sum(jnp.where(now > 0, dec + now, 0))
    one = now == 1
    if chunk_tile is None:
        # a row's passes less those wholly behind its first key, its own tokens from registers
        chunks = jnp.sum(jnp.where(
            now > 1, (_trips(dec, Lc) - first_key(dec, window) // Lc) * Lc + now, 0))
    else:
        at = jnp.arange(0, max(int(max_q_len), 1), chunk_tile, dtype=jnp.int32)[None, :]
        d, n = dec[:, None], now[:, None]                   # a tile's first token: ``at``
        walked = (_trips(d + jnp.minimum(n, at + chunk_tile), block_size)
                  - first_key(d + at, window) // block_size)
        chunks = jnp.sum(jnp.where((n > 1) & (at < n), walked, 0)) * block_size
    if kernel:
        ones = jnp.sum(jnp.where(one, _trips(dec + 1, block_size)
                                 - first_key(dec, window) // block_size, 0)) * block_size
    elif window is not None:
        _, live_t, cached = _one_token_tiles(dec, now)
        first = jnp.min(jnp.where(live_t, first_key(cached, window),
                                  jnp.iinfo(jnp.int32).max), axis=1) // Lc
        ones = (jnp.sum(jnp.maximum(_trips(jnp.max(cached, axis=1), Lc) - first, 0))
                * _ROW_TILE * Lc + jnp.sum(one))
    else:
        _, _, cached = _one_token_tiles(dec, now)
        ones = (jnp.sum(_trips(jnp.max(cached, axis=1), Lc)) * _ROW_TILE * Lc
                + jnp.sum(one))
    return (live.astype(jnp.int32), (ones + chunks).astype(jnp.int32),
            jnp.sum(one & kernel).astype(jnp.int32))


def _kernels(q_dtype, pool, bt, tokens: int, plain: bool):
    """(``decodes_in_kernel``, ``writes_in_kernel``, ``chunks_in_kernel``) asked with
    what the POOL says of itself (its rows, their width, the block size; a stacked
    pool's layer axis is not read): the ONE place the questions are put, for the call
    and for ``paged_counts``."""
    kv_rows, bs, lanes = pool.shape[-3:]
    sizes = dict(head_dim=lanes, block_size=bs, rows=bt.shape[0], blocks_per_seq=bt.shape[1])
    return (decodes_in_kernel(q_dtype, pool.dtype, plain=plain, **sizes),
            writes_in_kernel(pool.dtype, tokens=tokens, kv_heads=kv_rows, **sizes),
            chunks_in_kernel(q_dtype, pool.dtype, tokens=tokens, kv_heads=kv_rows,
                             plain=plain, **sizes))


def paged_counts(q_dtype, key_pool, dec, now, cu, bt, *, tokens: int, heads: int,
                 max_q_len: int, plain: bool = True, window=None):
    """What ONE cache layer's ``blha_attention`` call did, for a trunk's ``counts``:
    ``attention_positions``'s three, the chunk rows ``paged_chunk`` took and
    ``cache_write_counts``'s two, the kernels asked as the call asks them.
    ``q_dtype``: the queries' (``compute_dtype``); ``key_pool``: a layer's key pool
    as the trunk holds it (plain, lane-packed or stacked); ``tokens``: the packed
    buffer's; ``heads``, ``max_q_len``, ``window``: the call's ``num_heads`` and
    its own two; ``plain``: as ``decodes_in_kernel``'s."""
    decodes, writes, chunks = _kernels(q_dtype, key_pool, bt, tokens, plain)
    chunks = chunks and max_q_len > 1
    live, read, in_kernel = attention_positions(
        dec, now, block_size=key_pool.shape[-2], blocks_per_seq=bt.shape[1], kernel=decodes,
        window=window, max_q_len=max_q_len,
        chunk_tile=tile_tokens(heads // key_pool.shape[-3], max_q_len) if chunks else None)
    written, pieces = cache_write_counts(dec, now, cu, kernel=writes)
    return {"attn_positions_live": live, "attn_positions_read": read,
            "attn_rows_kernel": in_kernel,
            "attn_chunks_kernel": jnp.sum((now > 1) & chunks).astype(jnp.int32),
            "kv_write_tokens": written, "kv_write_blocks": pieces}


def _additive_bias(mask, tgt_mask, enc, now, S: int, width: int):
    """``mask`` (rows in prefill) and ``tgt_mask`` (decoder rows), each
    [B, 1|H, Sq, Lm] additive with the key axis aligned at column 0 (a
    pre-cache prefix takes the first columns, as in the reference's
    create_attn_mask), as ONE float32 bias [B, 1|H, S, width], zero where a
    mask does not reach or does not apply. None if neither was given."""
    def fit(m, rows):
        m = m.astype(jnp.float32)[:, :, :S, :width]
        m = jnp.pad(m, ((0, 0), (0, 0), (0, S - m.shape[2]),
                        (0, width - m.shape[3])))
        return jnp.where(rows[:, None, None, None], m, 0.0)

    parts = []
    if mask is not None:
        parts.append(fit(mask, enc > 0))
    if tgt_mask is not None:
        parts.append(fit(tgt_mask, (enc <= 0) & (now > 0)))
    return sum(parts) if parts else None


def _blocked_attention(q, k, v, key_cache, value_cache, enc, dec, now, cu,
                       block_tables, *, max_q_len: int, scale: float, quant: bool,
                       k_dequant, v_dequant, pre_k, pre_v, mask, tgt_mask, in_kernel: bool,
                       chunks: bool, window=None):
    """Steps 6-8 of ``blha_attention``: q [T, H, D] and this step's k, v
    [T, KV, D] against the pool, which already holds them. Returns
    [T, H, D] float32, zeros for tokens of no live row.  (Over a pool that
    packs heads into a lane tile, ``D`` and ``KV`` are the POOL's: the caller
    hands the packed problem, and ``scale`` is the heads' own.)

    A row attends, in this order and through one online softmax: its
    pre-cache, the cache positions [0, dec) a context block at a time, and
    this step's own tokens (causal among themselves) from k and v.  Under a
    ``window`` a query at position t attends ``t - window + 1 .. t``: a row's
    walk starts at the pass that holds its first query's first key and masks
    inside it, each query of a chunk by its own first key; what lies behind
    is not gathered, so the table may name no block there."""
    T, H, D = q.shape
    KV = k.shape[1]
    g = H // KV
    nb, _, bs, _ = key_cache.shape
    B, P = block_tables.shape
    S = int(max_q_len)
    per, Lc = _context_block(bs, P)
    bt = jnp.pad(block_tables, ((0, 0), (0, (-P) % per)), constant_values=-1)
    bt = jnp.where((bt < 0) | (bt >= nb), nb, bt)                  # -> nothing
    pools = (key_cache.reshape(nb * KV, bs, D),
             value_cache.reshape(nb * KV, bs, D))
    heads = jnp.arange(KV, dtype=jnp.int32)
    kpos = jnp.arange(Lc, dtype=jnp.int32)
    pre_len = 0 if pre_k is None else pre_k.shape[2]
    bias = _additive_bias(mask, tgt_mask, enc, now, S,
                          pre_len + bt.shape[1] * bs + S)

    def gather(ids):
        """Table columns ids [..., per] -> keys, values [..., KV, Lc, D]: a
        (block, kv head) is one row of the pool seen as [nb x KV, bs, D], so
        the gather lands in the order the products want. An int8 cache comes
        back as its integers less 128 in q's dtype (exact); ``scales`` turn
        the products into what the dequantised cache would give."""
        with jax.named_scope("kv_gather"):
            idx = ids[..., None, :] * KV + heads[:, None]          # [..., KV, per]

            def take(pool):
                blk = pool.at[idx].get(mode="fill",
                                       fill_value=128 if quant else 0)
                blk = blk.reshape(idx.shape[:-1] + (Lc, D))
                if quant:
                    blk = (blk.astype(jnp.float32) - 128.0).astype(q.dtype)
                return blk
            return take(pools[0]), take(pools[1])

    def scales(rows):
        if not quant:
            return None, None
        return tuple((sc if sc.ndim == 1 else sc[rows])[..., None, None]
                     for sc in (k_dequant, v_dequant))

    def attend(carry, qt, kb, vb, visible, bias_blk=None, kd=None, vd=None):
        """One block of the online softmax: queries qt [..., Q, D] against
        keys and values kb, vb [..., L, D]."""
        with jax.named_scope("scores"):
            s = jnp.einsum("...qd,...ld->...ql", qt, kb,
                           preferred_element_type=jnp.float32) * scale
            if kd is not None:
                s = s * kd
            if bias_blk is not None:
                s = s + bias_blk

        def pv(p, vv):
            o = jnp.einsum("...ql,...ld->...qd", p, vv,
                           preferred_element_type=jnp.float32)
            return o if vd is None else o * vd
        return _online(carry, s, visible, vb.astype(jnp.float32), pv)

    def cols(b, at, width):
        """Columns [at, at + width) of a row's bias b [..., W]; None if none."""
        return None if b is None else jax.lax.dynamic_slice_in_dim(
            b, at, width, axis=-1)

    def start(*lead):
        return (jnp.full(lead, _NEG, jnp.float32), jnp.zeros(lead, jnp.float32),
                jnp.zeros(lead + (D,), jnp.float32))

    def finish(carry):
        _, l, acc = carry
        return acc / jnp.maximum(l, 1e-30)[..., None]

    # ---- rows that feed one token ------------------------------------------
    first = jnp.clip(cu[:-1], 0, T - 1)
    q1 = q[first].reshape(B, KV, g, D)

    def one_token_tiles():
        """Tiles of ``_ROW_TILE`` rows of like length, each as many passes as
        its longest row needs; this step's token from registers."""
        k1, v1 = k[first][:, :, None], v[first][:, :, None]        # [B, KV, 1, D]
        rows_t, live_t, cached_t = _one_token_tiles(dec, now)

        def tile(t, out):
            rows, live, cached = rows_t[t], live_t[t], cached_t[t]
            qt, ids = q1[rows], bt[rows]
            kd, vd = scales(rows)
            on = live[:, None, None, None]
            b = None
            if bias is not None:
                b = bias[rows, :, 0]                               # [R, 1|H, W]
                b = b.reshape((_ROW_TILE,)
                              + ((KV, g) if b.shape[1] == H else (1, 1))
                              + b.shape[-1:])
            carry = start(_ROW_TILE, KV, g)
            if pre_k is not None:
                carry = attend(carry, qt, pre_k[rows].astype(k.dtype),
                               pre_v[rows].astype(v.dtype), on, cols(b, 0, pre_len))
            lo = first_key(cached, window)         # this step's token is at ``cached``

            def block(j, carry):
                kb, vb = gather(
                    jax.lax.dynamic_slice_in_dim(ids, j * per, per, axis=1))
                at = (j * Lc + kpos)[None, :]
                vis = at < cached[:, None]
                if window is not None:
                    vis = vis & (at >= lo[:, None])
                return attend(carry, qt, kb, vb, vis[:, None, None, :],
                              cols(b, pre_len + j * Lc, Lc), kd, vd)

            first = (0 if window is None else
                     jnp.min(jnp.where(live, lo, jnp.iinfo(jnp.int32).max)) // Lc)
            carry = jax.lax.fori_loop(first, _trips(jnp.max(cached), Lc), block, carry)
            bb = None if b is None else jnp.take_along_axis(
                b, (pre_len + cached)[:, None, None, None], axis=-1)
            o = finish(attend(carry, qt, k1[rows], v1[rows], on, bb))
            return out.at[jnp.where(live, cu[rows], T + S)].set(
                o.reshape(_ROW_TILE, H, D), mode="drop")

        # the order puts these rows first, so the tiles past them hold none
        return jax.lax.fori_loop(
            0, _trips(jnp.sum(now == 1).astype(jnp.int32), _ROW_TILE), tile,
            jnp.zeros((T + S, H, D), jnp.float32))

    Hp = padded_heads(H) if chunks and S > 1 else H
    if in_kernel:
        # straight from the pool, which the write has already given this
        # step's token in the value the XLA pass attends from registers
        # (``fresh_dt``): positions [0, dec], no gathered copy, a row's own trips
        o = paged_decode(q1, key_cache, value_cache,
                         jnp.where(now == 1, dec + 1, 0), block_tables, scale=scale,
                         window=window)
        # (where the chunk rows' kernel follows, a token's heads as ITS copies
        # want them: whole tiles)
        out = jnp.zeros((T + S, Hp, D), jnp.float32).at[
            jnp.where(now == 1, cu[:-1], T + S), :H].set(o.reshape(B, H, D), mode="drop")
    else:
        out = one_token_tiles()
        if Hp > H:
            out = jnp.pad(out, ((0, 0), (0, Hp - H), (0, 0)))
    if S == 1:
        return out[:T]
    if chunks:
        # all of them in ONE call, straight from the pool as the one-token
        # rows' kernel reads it: a row's own blocks by the table, from the block
        # of its first token's first key
        return paged_chunk(q, key_cache, value_cache, out, dec, now, cu, block_tables,
                           scale=scale, max_q_len=S, window=window)[:T, :H]

    # ---- rows that feed a chunk: one at a time ------------------------------
    chunk_rows = jnp.nonzero(now > 1, size=B, fill_value=0)[0].astype(jnp.int32)
    tail = ((0, S), (0, 0), (0, 0))
    q_pad, k_pad, v_pad = jnp.pad(q, tail), jnp.pad(k, tail), jnp.pad(v, tail)
    qi = jnp.arange(S, dtype=jnp.int32)

    def chunk_row(i, out):
        r = chunk_rows[i]
        at, base, nq = cu[r], dec[r], now[r]

        def own(x):     # [T + S, heads, D] -> this row's [heads, S, D]
            return jnp.swapaxes(jax.lax.dynamic_slice_in_dim(x, at, S, axis=0), 0, 1)

        qt = own(q_pad).reshape(KV, g * S, D)          # a kv head's g x S queries
        ids = bt[r]
        kd, vd = scales(r)
        b = None
        if bias is not None:
            b = bias[r]                                            # [1|H, S, W]
            b = (b.reshape(KV, g * S, -1) if b.shape[0] == H
                 else jnp.tile(b, (1, g, 1)))
        carry = start(KV, g * S)
        if pre_k is not None:
            carry = attend(carry, qt, pre_k[r].astype(k.dtype),
                           pre_v[r].astype(v.dtype), True, cols(b, 0, pre_len))

        if window is not None:
            sq = jnp.tile(qi, g)[:, None]               # a query's place in the chunk

        def block(j, carry):
            kb, vb = gather(jax.lax.dynamic_slice_in_dim(ids, j * per, per))
            at = j * Lc + kpos
            vis = at < base
            if window is not None:                      # each query its own first key
                vis = vis[None, :] & (at[None, :] > base + sq - window)
            return attend(carry, qt, kb, vb, vis,
                          cols(b, pre_len + j * Lc, Lc), kd, vd)

        carry = jax.lax.fori_loop(first_key(base, window) // Lc, _trips(base, Lc), block, carry)
        # this step's tokens: causal inside the chunk
        if window is None:
            sq = jnp.tile(qi, g)[:, None]
        own_k, own_v = own(k_pad), own(v_pad)
        vis = (qi[None, :] <= sq) & (qi[None, :] < nq)
        if window is not None:
            vis = vis & (qi[None, :] > sq - window)
        carry = attend(carry, qt, own_k, own_v, vis, cols(b, pre_len + base, S))
        o = jnp.swapaxes(finish(carry).reshape(H, S, D), 0, 1)     # [S, H, D]
        old = jax.lax.dynamic_slice_in_dim(out, at, S, axis=0)
        o = jnp.where((qi < nq)[:, None, None], o, old)
        return jax.lax.dynamic_update_slice_in_dim(out, o, at, axis=0)

    out = jax.lax.fori_loop(0, jnp.sum(now > 1).astype(jnp.int32), chunk_row, out)
    return out[:T]


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
    "has_out_quant", "window"))
@jax.named_scope("paged_attention")
def blha_attention(
    qkv,                       # [T, (H+2*KV)*D] float/bf16 (or int32 w/ qkv_out_scale)
    key_cache,                 # [NB, KV, bs, D] (uint8 when cache_quant) | [NB, KV/pack, bs, D*pack]
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
    layer=None,                # int32 scalar: which layer of a stacked pool
    window=None,               # static: a query at t attends t - window + 1 .. t (None: 0 .. t)
):
    """One serving attention step over the paged cache.

    The pools may carry a leading layer axis, ``[layers, NB, KV, bs, D]``,
    with ``layer`` (data: a loop's counter) naming the one this call writes
    and reads.  The stacked pool is then seen as ``layers x NB`` blocks in
    its own row-major order, which costs nothing, and the table's entries
    are moved to the layer's blocks: the write, the gather and the
    ``paged_decode`` kernel go by block number and touch no other layer's.

    ``window`` (static): a sliding window over the context. It is no mask a
    kernel must refuse: it is a first block and a first position. The blocked
    pass and ``paged_decode`` start a row's walk at the block that holds its
    first key and mask inside it, so the table's entries behind the window may
    name no block (an engine gives them back: inference/serving_model.py).

    Returns (out [T, H*D], key_cache', value_cache',
             k_quant_scales', v_quant_scales', k_dequant_scales',
             v_dequant_scales') — scale arrays pass through unchanged except
    in dynamic quant mode, where prefill rows refresh them.

    Scopes (children of ``paged_attention``): ``rope``, ``kv_write`` (on the
    chip it holds the ``paged_write`` custom call), and
    inside the loops over row tiles, chunk rows and context blocks
    (``while/body/``) ``kv_gather`` (a block's gather, an int8 block's
    integers), ``scores`` (QK^T, masks, the online softmax's bookkeeping),
    ``values`` (PV); what is under none is unpacking, token coordinates and
    the return to the packed buffer. Where rows go through a kernel they
    are in none of the three: a device trace shows the custom call by its
    name, ``paged_decode`` (one-token rows) or ``paged_chunk`` (chunk rows).
    """
    H, KV, D, bs = num_heads, kv_num_heads, head_dim, block_size
    T = qkv.shape[0]
    B = block_tables.shape[0]
    # the pool says how it lays its heads: ``pack`` of them a lane tile where
    # its rows are as wide as ``lane_packing`` makes them, else one
    pack, _ = lane_packing(KV, D)
    if key_cache.shape[-1] != D * pack:
        pack = 1
    elif pack > 1 and cache_quant != "none":
        raise ValueError(
            f"a pool that packs {pack} heads of {D} into a lane tile is not quantised: its "
            f"scales are a head's, and a row of {tuple(key_cache.shape[-3:])} holds {pack}")
    stacked = None
    if layer is not None:
        if key_cache.ndim != 5:
            raise ValueError(
                f"blha_attention(layer=) names a layer of a stacked pool [layers, NB, KV, bs, D];"
                f" this pool is {tuple(key_cache.shape)}")
        stacked = layers, per_layer = key_cache.shape[:2]
        key_cache = key_cache.reshape((layers * per_layer,) + key_cache.shape[2:])
        value_cache = value_cache.reshape(key_cache.shape)
        block_tables = jnp.where(
            (block_tables >= 0) & (block_tables < per_layer),
            block_tables + jnp.asarray(layer, jnp.int32) * per_layer, -1)
    elif key_cache.ndim == 5:
        raise ValueError("a stacked pool [layers, NB, KV, bs, D] needs layer= to say which is meant")

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

        # ---- 5. K/V into the block pool -------------------------------------
        decodes, by_row, chunks = _kernels(
            q.dtype, key_cache, block_tables, T, plain=cache_quant == "none"
            and mask is None and tgt_mask is None and pre_key_cache is None)
        by_row = by_row and cache_quant == "none"
        if not by_row:      # the scatter's coordinates, where the parent had them
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
            # heads that share a lane tile lie side by side in the packed
            # buffer already: the pool's rows are a view of it
            k_store = k.astype(key_cache.dtype).reshape(T, KV // pack, D * pack)
            v_store = v.astype(value_cache.dtype).reshape(T, KV // pack, D * pack)
        if by_row:
            # row by row into the pieces of the blocks a row holds, in place:
            # what is moved follows what is live (module docstring)
            key_cache, value_cache = paged_write(
                k_store, v_store, key_cache, value_cache, seq_lens_decoder,
                seq_lens_this_time, cu_seqlens_q, block_tables)
        else:
            # a scatter over the packed buffer by (block, kv head, slot), a
            # head's D values an update: the layout the gather reads, so the
            # pool keeps one layout; a dead token's window is walked, sent
            # to block nb and dropped
            hd = jnp.arange(KV // pack, dtype=jnp.int32)[None, :]
            key_cache = key_cache.at[blk[:, None], hd, slot[:, None]].set(
                k_store, mode="drop")
            value_cache = value_cache.at[blk[:, None], hd, slot[:, None]].set(
                v_store, mode="drop")

    # ---- 6-8. attention, blocked over the context -----------------------
    # this step's own keys and values are attended from registers, as one
    # trailing block: unquantised whatever the cache stores (the reference
    # kernel keeps prefill outputs exact that way), and rounded to the
    # cache's dtype where it is not quantised, which is what a read of the
    # cache would return; so the cache is read for positions [0, dec) only
    quant = cache_quant != "none"
    fresh_dt = q.dtype if quant else key_cache.dtype
    k, v = k.astype(fresh_dt), v.astype(fresh_dt)
    if pack > 1:
        # ``pack`` KV heads of D are ONE of D * pack to everything below, with
        # a group of pack * g query heads whose queries are zero outside their
        # own head's lanes: the zeros add exact zeros to the scores, and
        # ``p . v`` gives a query head its own head's values in its own lanes
        # and a finite product, dropped here, in the others
        own = _own_lanes(H // KV, pack, H)[:, :, None]             # [H, pack, 1]
        q = jnp.where(own, q[:, :, None, :], 0).reshape(T, H, D * pack)
        k, v = (x.reshape(T, KV // pack, D * pack) for x in (k, v))
        if pre_key_cache is not None:
            pre_key_cache, pre_value_cache = (
                jnp.swapaxes(x.reshape(B, KV // pack, pack, -1, D), 2, 3).reshape(
                    B, KV // pack, -1, D * pack)
                for x in (pre_key_cache, pre_value_cache))
    out = _blocked_attention(
        q, k, v, key_cache, value_cache,
        seq_lens_encoder, seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
        block_tables, max_q_len=max_q_len, scale=1.0 / (D ** 0.5), quant=quant,
        k_dequant=cache_k_dequant_scales, v_dequant=cache_v_dequant_scales,
        pre_k=pre_key_cache, pre_v=pre_value_cache, mask=mask,
        tgt_mask=tgt_mask, in_kernel=decodes, chunks=chunks, window=window)
    if pack > 1:
        out = jnp.sum(jnp.where(own, out.reshape(T, H, pack, D), 0), axis=2)
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
    if stacked is not None:
        key_cache = key_cache.reshape(stacked + key_cache.shape[1:])
        value_cache = value_cache.reshape(key_cache.shape)
    return (out, key_cache, value_cache,
            cache_k_quant_scales, cache_v_quant_scales,
            cache_k_dequant_scales, cache_v_dequant_scales)
