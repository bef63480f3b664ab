"""The held experts of a sparse layer: what the experts ``lo .. lo + n_held``
give of the routed result, whichever model routes (models/pangu_moe.py
``_moe_ffn``).  The picks on a held expert, sorted by expert and padded to
whole tiles, go through ``grouped_experts`` (the Pallas kernel ``expert_gmm``)
where ``groups_in_kernel`` admits the call, else through ``_tile_loop``."""
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..device import on_tpu
from .pallas.expert_gmm import expert_gmm

F32 = jnp.float32


# what stands between an expert's gate product and its up product, by name
# (static in ``_held_experts``'s trace): SwiGLU's ``silu``, ReGLU's ``relu``
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _glu(x, wg, wu, wd, act=jax.nn.silu):
    return (act(x @ wg) * (x @ wu)) @ wd


def _swiglu(x, wg, wu, wd):
    return _glu(x, wg, wu, wd)


# the grouped product (ops/pallas/expert_gmm.py).  The layout of the sorted rows
# follows the call's geometry (``layout``); these are the rule's bounds: the row
# tiles it chooses among, the tiles a chunk keeps beyond the expected ones (for
# experts that outgrow a tile), the bytes of gathered rows a chunk holds at
# least (a bound that small is one chunk), and the rows whose tokens the
# kernel holds in scalar memory
_ROW_TILES = (32, 64, 128)
_SPARE_TILES = 8
_CHUNK_BYTES = 16 << 20
_ROW_WORDS = 1 << 17


def _bound(picks: int, n_held: int, row_tile: int) -> int:
    """Tiles of ``row_tile`` rows that hold ``picks`` sorted rows whatever the
    routing, every held expert's rows starting on a tile."""
    return -(-picks // row_tile) + n_held


def layout(tokens: int, k: int, n_held: int, routed: int, row_bytes: int) -> Tuple[int, int, int]:
    """How a call's sorted picks are laid out, from what the call shows and
    nothing else: ``tokens`` x ``k`` picks over ``routed`` experts of which
    ``n_held`` are held here, rows of ``row_bytes``.  -> (rows of a tile, tiles
    that hold the picks whatever the routing, tiles of one chunk).

    The ROW TILE is the least of ``_ROW_TILES`` that holds the rows an expert
    expects under even routing (``tokens x k / routed``), so an expert is ONE
    tile: the kernel's weight block is indexed by the tile's expert and its
    pipeline looks one grid step ahead, so with one tile an expert the next
    expert's matrix is on its way while this one's rows multiply, and with two
    it starts only at the second (ISSUE 47: 64 experts of 48 rows at tiles of
    32 read at 60 % of the bandwidth, at tiles of 64 at chat-batch's share).
    The CHUNK (the tiles gathered and multiplied at a time; its static size is
    the layer's temporaries) holds the tiles the expected picks need, one an
    expert's tile-full, and ``_SPARE_TILES`` more, and at least what
    ``_CHUNK_BYTES`` of rows hold; never more than the bound.  So the loop
    over chunks runs once unless routing piles up, and then again: nothing is
    dropped."""
    rows = -(-tokens * k // routed)
    row_tile = next((t for t in _ROW_TILES if rows <= t), _ROW_TILES[-1])
    bound = _bound(tokens * k, n_held, row_tile)
    expected = n_held * -(-rows // row_tile) + _SPARE_TILES
    return row_tile, bound, min(bound, max(expected, _CHUNK_BYTES // (row_tile * row_bytes)))


def groups_in_kernel(x_dtype, w_dtype, *, hidden: int, width: int, rows: int) -> bool:
    """Whether a call's sorted picks go through the Pallas grouped product
    (``ops/pallas/expert_gmm.py``), decided from what the call shows and
    nothing else, as ``ops/paged_attention.decodes_in_kernel`` decides: the
    platform is the TPU; the rows and the experts' matrices are bfloat16;
    ``hidden`` (E) and ``width`` (F) are whole 128-lane tiles; a chunk's
    ``rows`` (their tokens: ``layout``'s row tile x its chunk, so they follow
    the call's geometry as the layout does) fit the kernel's scalar memory.
    Anything else
    (the CPU, the float32 eager ``forward``, the tiny test geometries) takes
    the tile loop."""
    return (on_tpu() and jnp.dtype(x_dtype) == jnp.bfloat16
            and jnp.dtype(w_dtype) == jnp.bfloat16
            and hidden % 128 == 0 and width % 128 == 0 and rows <= _ROW_WORDS)


def _tiles_of(sizes, tile):
    """Tiles of ``tile`` rows in use when every expert's rows start on one."""
    return jnp.sum((sizes + tile - 1) // tile)


def _tile_rows(i, tile, sizes, first_row):
    """Tiles ``i`` (one or a vector of them) in that layout -> (the expert of
    each, its rows' places in the sorted order ``[..., tile]``, which of them
    are picks: none of a tile past the last in use)."""
    tiles = (sizes + tile - 1) // tile
    last_tile = jnp.cumsum(tiles)
    e = jnp.minimum(jnp.searchsorted(last_tile, i, side="right"), sizes.shape[0] - 1)
    r = (first_row[e] + (i - (last_tile[e] - tiles[e])) * tile)[..., None] + jnp.arange(tile)
    return e, r, r < (first_row + sizes)[e][..., None]


def _tile_loop(x, tok_s, w_s, sizes, first_row, eg, eu, ed, tile, act=jax.nn.silu):
    """One tile of ``tile`` rows at a time through its expert's gated unit, a
    ``fori_loop`` over the tiles in use. -> (y [T, E] float32, tiles in use)."""
    T, E = x.shape
    n_tiles = _tiles_of(sizes, tile)
    x_pad = jnp.concatenate([x, jnp.zeros((1, E), x.dtype)])

    def one_tile(i, out):
        e, r, ok = _tile_rows(i, tile, sizes, first_row)
        r = jnp.clip(r, 0, tok_s.shape[0] - 1)
        t = jnp.where(ok, tok_s[r], T)
        y = _glu(x_pad[t], eg[e], eu[e], ed[e], act).astype(F32)
        return out.at[t].add(y * jnp.where(ok, w_s[r], 0.0)[:, None], mode="drop")

    return jax.lax.fori_loop(0, n_tiles, one_tile, jnp.zeros((T, E), F32)), n_tiles


def grouped_experts(x, tok_s, w_s, sizes, first_row, eg, eu, ed, *, row_tile, chunk,
                    gmm=expert_gmm, act=jax.nn.silu):
    """The sorted picks through ONE grouped product an expert matrix.  Every
    expert's rows start on a row tile, so a tile is one expert's and its spare
    rows are zeros; the tiles in use come first.  The rows are gathered into
    that order once and go through gate, up and down (``gmm``, the kernel of
    ``ops/pallas/expert_gmm.py``, brings each touched expert's block from the
    stack once; float32 results, the activation in float32); the down product
    adds its weighted rows to their tokens in the kernel (``combine``), so
    nothing is scattered.  ``row_tile`` and ``chunk`` are ``layout``'s: the
    rows of a tile follow the rows an expert expects, so that an expert is one
    tile, and a CHUNK of tiles at a time follows the picks the call expects
    here and not ``T x k`` (15 of 16 sorted rows are picks of experts held
    elsewhere in a 16-of-256 deployment; every one is live where all are
    held), so the loop over chunks runs once unless the picks pile up, and is
    no loop where a chunk holds the bound.
    -> (y [T, E] float32, tiles in use)."""
    T, E = x.shape
    n_held, R = eg.shape[0], tok_s.shape[0]
    n_tiles = _tiles_of(sizes, row_tile)
    x_pad = jnp.concatenate([x, jnp.zeros((1, E), x.dtype)])
    gmm = functools.partial(gmm, row_tile=row_tile)

    def one_chunk(c):
        e, r, ok = _tile_rows(c * chunk + jnp.arange(chunk), row_tile, sizes, first_row)
        r, ok = jnp.clip(r, 0, R - 1).reshape(-1), ok.reshape(-1)
        n = jnp.clip(n_tiles - c * chunk, 0, chunk)
        xs = x_pad[jnp.where(ok, tok_s[r], T)]
        h = act(gmm(xs, eg, e, n)) * gmm(xs, eu, e, n)
        return gmm(h.astype(x.dtype), ed, e, n,
                   combine=(tok_s[r], jnp.where(ok, w_s[r], 0.0), T))

    if chunk >= _bound(R, n_held, row_tile):
        return one_chunk(0), n_tiles
    y = jax.lax.fori_loop(0, (n_tiles + chunk - 1) // chunk,
                          lambda c, out: out + one_chunk(c), jnp.zeros((T, E), F32))
    return y, n_tiles


@functools.partial(jax.jit, static_argnames=("lo", "tile", "gmm", "grouping", "activation"))
@jax.named_scope("experts")
def _held_experts(x, idx, w, eg, eu, ed, valid, *, lo, tile, gmm, grouping,
                  activation="silu"):
    """``held_experts`` without its counting, a jitted function: the expert
    layers of a program (and the programs of a process that feed the same
    shapes) share ONE trace, and a program lowers it, its kernels with it,
    once and calls it a layer.  ``gmm``: the grouped product's kernel where
    the call is admitted (static: part of what the trace is cached under),
    else None (the tile loop, ``tile`` rows a tile); ``grouping``: ``layout``'s
    (row tile, chunk) for the kernel.
    -> (y, picks, held experts with a row, tiles in use, rows multiplied)."""
    T, E = x.shape
    n_held, k = eg.shape[0], idx.shape[1]
    le = idx - lo
    hit = (le >= 0) & (le < n_held)
    if valid is not None:
        hit = hit & valid[:, None]
    le = jnp.where(hit, le, n_held).reshape(-1)
    order = jnp.argsort(le, stable=True)
    tok_s = (order // k).astype(jnp.int32)
    w_s = w.reshape(-1)[order]
    sizes = jnp.sum(le[:, None] == jnp.arange(n_held)[None, :], axis=0).astype(jnp.int32)
    first_row = jnp.cumsum(sizes) - sizes                 # in the sorted order
    act = ACTIVATIONS[activation]
    if gmm is None:
        tile = min(tile, T * k)
        y, tiles = _tile_loop(x, tok_s, w_s, sizes, first_row, eg, eu, ed, tile, act)
    else:
        tile, chunk = grouping
        y, tiles = grouped_experts(x, tok_s, w_s, sizes, first_row, eg, eu, ed,
                                   row_tile=tile, chunk=chunk, gmm=gmm, act=act)
    return (y, jnp.sum(hit).astype(jnp.int32), jnp.sum(sizes > 0).astype(jnp.int32),
            tiles, tiles * tile)


def held_experts(x, idx, w, eg, eu, ed, lo, valid=None, tile=128, counts=None,
                 activation="silu", routed=None):
    """The part of the routed result that the held experts give.

    x [T, E]; idx, w [T, k] from ``route``; eg, eu [n_held, E, F], ed
    [n_held, F, E]: the experts ``lo .. lo + n_held`` of the published range;
    ``routed``: the experts the router chooses among (its width; every one
    held where None).  The picks that fall on a held expert are sorted by
    expert and each expert's rows padded to whole tiles; an expert no token
    picked is not read.  Where ``groups_in_kernel`` admits the call the tiles
    go through ``grouped_experts`` (one grouped product an expert matrix, rows
    a tile and tiles a chunk by ``layout``: from T, k, ``n_held`` and
    ``routed``), else one tile of ``tile`` rows at a time through its expert's
    gated unit (``_tile_loop``).
    ``activation`` names what stands between the gate and the up product
    (``ACTIVATIONS``: ``silu`` a SwiGLU, ``relu`` a ReGLU).
    Nothing is dropped: there is no capacity.
    -> (y [T, E] float32, picks that fell on a held expert).
    ``counts``: a trunk's dict of int32 scalars; to those of these names it
    holds, the call adds what it did: ``experts_touched`` (held experts with
    at least one row), ``expert_tiles`` (tiles in use: over
    ``experts_touched``, tiles an expert, 1 where the layout fits the
    routing), ``expert_tile_rows`` (rows the products multiplied, padding
    included), ``expert_tile_rows_live`` (the picks among them) and
    ``expert_rows_grouped`` (the picks that went through the grouped
    product: 0 from a call that took the tile loop)."""
    (T, E), n_held, k = x.shape, eg.shape[0], idx.shape[1]
    row_tile, _, chunk = layout(T, k, n_held, routed or n_held, E * x.dtype.itemsize)
    grouped = groups_in_kernel(x.dtype, eg.dtype, hidden=E, width=eg.shape[2],
                               rows=row_tile * chunk)
    y, picks, touched, tiles, rows = _held_experts(
        x, idx, w, eg, eu, ed, valid, lo=int(lo), tile=int(tile),
        gmm=expert_gmm if grouped else None, grouping=(row_tile, chunk),
        activation=activation)
    for name, n in (("experts_touched", touched), ("expert_tiles", tiles),
                    ("expert_tile_rows", rows), ("expert_tile_rows_live", picks),
                    ("expert_rows_grouped", picks * grouped)):
        if counts is not None and name in counts:
            counts[name] += n
    return y, picks
