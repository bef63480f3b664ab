"""What a launch carries across the host-device boundary: ONE ``int32``
array each way.

Up, the engine's control rows (``[B]``-shaped scheduling state, the block
table, a mixed scan's prompt window) laid out one after another: float rows
by their bits, flags as 0/1, matrices flattened.  Down, what the host reads
of a launch (tokens, the emit mask, final prefill positions, accepted draft
lengths, the trunk's counts).  A :class:`Layout` is the static description
both sides share: the host packs with numpy, the compiled program slices
with static offsets (``lax.bitcast_convert_type`` for the float rows), so a
layout adds no recompile axis.  A :class:`ResultBlock` is a program's output
that carries its layout as pytree metadata: the host needs no second source
for the names and shapes of what came down.

A count rides as one ``int32`` word: none passes 2**31 in one launch today
(the largest, ``dsa_positions_scored``, is 7e7 at 512 tokens x 17 k x 8);
one that could would go as two words, not wrap."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Layout", "ResultBlock"]


@dataclass(frozen=True)
class Layout:
    """``rows``: ((name, shape, kind), ...) in block order; ``kind`` is
    ``"i"`` int32, ``"f"`` float32 (crosses by its bits) or ``"b"`` a flag
    (crosses as 0/1)."""
    rows: Tuple[Tuple[str, Tuple[int, ...], str], ...]

    @classmethod
    def of(cls, *rows) -> "Layout":
        """Rows as ``(name, shape)`` (int32) or ``(name, shape, kind)``."""
        return cls(tuple((r[0], tuple(int(n) for n in r[1]),
                          r[2] if len(r) > 2 else "i") for r in rows))

    @property
    def size(self) -> int:
        return sum(math.prod(shape) for _, shape, _ in self.rows)

    def pack(self, arrays: Dict[str, object]):
        """{name: array} -> the block.  numpy rows pack on the host, traced
        rows in the program; a row of the wrong shape, a missing or a stray
        name raises."""
        if set(arrays) != {name for name, _, _ in self.rows}:
            raise ValueError(f"block rows {sorted(arrays)} are not the layout's "
                             f"{[name for name, _, _ in self.rows]}")
        host = all(isinstance(a, np.ndarray) for a in arrays.values())
        words = []
        for name, shape, kind in self.rows:
            a = arrays[name]
            if tuple(a.shape) != shape:
                raise ValueError(f"block row {name!r} has shape {tuple(a.shape)}, "
                                 f"the layout says {shape}")
            if kind == "f":
                a = (np.ascontiguousarray(a, np.float32).view(np.int32) if host
                     else jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32))
            else:
                a = a.astype(np.int32) if host else a.astype(jnp.int32)
            words.append(a.reshape(-1))
        return np.concatenate(words) if host else jnp.concatenate(words)

    def unpack(self, block) -> Dict[str, object]:
        """The block -> {name: array}, each row in its own shape and type:
        numpy views of a numpy block, static slices of a traced one."""
        if block.shape != (self.size,):
            raise ValueError(f"a block of shape {block.shape} under a layout "
                             f"of {self.size} words")
        host = isinstance(block, np.ndarray)
        out, at = {}, 0
        for name, shape, kind in self.rows:
            n = math.prod(shape)
            a = block[at:at + n].reshape(shape)
            if kind == "f":
                a = (a.view(np.float32) if host
                     else jax.lax.bitcast_convert_type(a, jnp.float32))
            elif kind == "b":
                a = a != 0
            out[name] = a
            at += n
        return out


@jax.tree_util.register_pytree_node_class
class ResultBlock:
    """A launch's result words with the layout that reads them: one array
    leaf to a jitted program, the layout its static metadata."""

    def __init__(self, words, layout: Layout):
        self.words, self.layout = words, layout

    @classmethod
    def of(cls, rows: Dict[str, object], counts: Dict[str, object]) -> "ResultBlock":
        """In the program: ``rows`` in their order, then the trunk's summed
        ``counts`` in the order of their names."""
        arrays = dict(rows)
        arrays.update({name: jnp.asarray(counts[name], jnp.int32).reshape(())
                       for name in sorted(counts)})
        layout = Layout.of(*((name, a.shape, "b" if a.dtype == jnp.bool_ else "i")
                             for name, a in arrays.items()))
        return cls(layout.pack(arrays), layout)

    def read(self) -> Tuple[Dict[str, np.ndarray], Dict[str, int]]:
        """On the host: ONE blocking read -> (rows, counts); the counts are
        the block's scalar rows."""
        rows = self.layout.unpack(np.asarray(self.words))
        counts = {name: int(rows.pop(name))
                  for name, shape, _ in self.layout.rows if shape == ()}
        return rows, counts

    def tree_flatten(self):
        return (self.words,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], layout)
