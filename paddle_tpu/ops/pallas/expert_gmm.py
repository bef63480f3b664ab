"""Grouped matmul for the held experts — Pallas TPU kernel that multiplies
row tiles by the expert matrix each belongs to (``ops/held_experts.py``
chooses the calls that take it).

``x`` [tiles * row_tile, K] holds the picks sorted by expert, every expert's
rows starting on a tile: tile ``i`` is all of expert ``tile_expert[i]``, its
spare rows zeros.  ``w`` [n_held, K, N] stays where it lies in HBM: the
weights' ``BlockSpec`` indexes the stack by the tile's expert (scalar
prefetch), so Pallas's pipeline has the next tile's block on its way while
this one multiplies, and consecutive tiles of one expert bring its block
once.  The pipeline looks ONE grid step ahead: an expert of one tile has the
next expert's matrix arriving while its rows multiply, and the call is bound
by the read; an expert of two starts the next matrix at its SECOND tile, and
the next expert's first tile then multiplies with nothing in flight (64
experts of ``[2560, 768]`` and 44 rows each: 0.41 ms a product at tiles of 32,
0.335 at tiles of 64; PERF.md section 6, PR 47).  So ``row_tile`` is the
caller's, chosen from the call's geometry so that an expert is one tile
(``ops/held_experts.py`` ``layout``); the kernel takes any.  The grid runs over the column blocks and then the ``n_tiles`` tiles in
use (a traced number): what the kernel reads follows the experts that have a
row, and a tile past the last live one costs nothing and is not written.
One product a call, float32 accumulation over all of K.  The last product of
a layer does not write its rows out: it weights them and adds them to their
tokens' rows of the result, a column block of which stays in VMEM while the
tiles pass (``combine``), so no scatter follows the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["expert_gmm", "column_block", "VMEM_LIMIT"]

VMEM_LIMIT = 64 << 20       # of a v5e core's 128 MiB
_BLOCK_BYTES = 4 << 20      # a weight block [K, columns]; two are in flight


def column_block(K: int, N: int, itemsize: int, tokens: int = 0) -> int:
    """Columns of a weight block: the most whole 128-lane tiles that divide
    ``N`` and keep ``[K, columns]`` within ``_BLOCK_BYTES`` (all of a width
    that is no whole tile); where the call combines, also the float32
    ``[tokens, columns]`` that stays in VMEM."""
    fits = [c for c in range(128, N + 1, 128)
            if N % c == 0 and max(K * itemsize, tokens * 4) * c <= _BLOCK_BYTES]
    return max(fits, default=128 if N % 128 == 0 else N)


def _product(x_ref, w_ref):
    # the package pins jax_default_matmul_precision=highest; Mosaic refuses
    # that on 16-bit operands, whose product in float32 is exact already
    precision = jax.lax.Precision.DEFAULT if x_ref.dtype == jnp.bfloat16 else None
    return jnp.dot(x_ref[...], w_ref[...], precision=precision,
                   preferred_element_type=jnp.float32)


def _kernel(tile_expert_ref, x_ref, w_ref, o_ref):
    del tile_expert_ref
    o_ref[...] = _product(x_ref, w_ref)


def _combining_kernel(tile_expert_ref, token_ref, x_ref, w_ref, weight_ref, zeros_ref, o_ref):
    del tile_expert_ref, zeros_ref
    i = pl.program_id(1)
    rows = x_ref.shape[0]

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    y = _product(x_ref, w_ref) * weight_ref[...]
    for r in range(rows):
        t = token_ref[i * rows + r]
        o_ref[pl.ds(t, 1), :] += y[r:r + 1, :]


def expert_gmm(x, w, tile_expert, n_tiles, *, row_tile: int, columns=None,
               combine=None, interpret: bool = False):
    """x [tiles * row_tile, K] @ w[tile_expert[i]] [K, N] a tile ->
    [tiles * row_tile, N] float32; tiles ``>= n_tiles`` are neither read nor
    written (their rows of the result are whatever the buffer held).

    ``combine`` = (token [rows] int32, weight [rows] float32, T): the rows'
    products are not written out but weighted and added to their tokens' rows
    of a float32 ``[T, N]`` (a column block of it stays in VMEM while the
    tiles pass): -> sum over rows r of weight[r] * (x[r] @ w[expert of r]) at
    token[r].  A spare row has weight 0 and any token in range; with no tile
    in use the result is zeros."""
    M, K = x.shape
    N = w.shape[2]
    columns = columns or column_block(K, N, w.dtype.itemsize,
                                      combine[2] if combine else 0)
    x_spec = pl.BlockSpec((row_tile, K), lambda j, i, *_: (i, 0))
    w_spec = pl.BlockSpec((None, K, columns), lambda j, i, te, *_: (te[i], 0, j))
    te = tile_expert.astype(jnp.int32)
    if combine is None:
        kernel, scalars, operands, aliases = _kernel, (te,), (x, w), {}
        in_specs = [x_spec, w_spec]
        out_spec = pl.BlockSpec((row_tile, columns), lambda j, i, te: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N), jnp.float32)
    else:
        token, weight, T = combine
        kernel, scalars = _combining_kernel, (te, token.astype(jnp.int32))
        operands = (x, w, weight.astype(jnp.float32)[:, None], jnp.zeros((T, N), jnp.float32))
        in_specs = [x_spec, w_spec, pl.BlockSpec((row_tile, 1), lambda j, i, *_: (i, 0)),
                    pl.BlockSpec(memory_space=pl.ANY)]
        out_spec = pl.BlockSpec((T, columns), lambda j, i, *_: (0, j))
        out_shape = jax.ShapeDtypeStruct((T, N), jnp.float32)
        aliases = {len(scalars) + 3: 0}     # the zeros ARE the result where no tile runs
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(N // columns, n_tiles),
            in_specs=in_specs, out_specs=out_spec),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT),
        name="expert_gmm",
        interpret=interpret,
    )(*scalars, *operands)
