"""The repo's two spellings of ``jax.shard_map``: fully manual, and manual
over some mesh axes with the rest left to GSPMD. The replication check is
off in both (``check_vma=False``). Used by the eager collectives, ring
attention, the expert all-to-all and the compiled pipeline."""
from __future__ import annotations

import jax

__all__ = ["shard_map_compat", "shard_map_manual",
           "partial_manual_supported"]


def partial_manual_supported(mesh, manual_axes) -> bool:
    """Whether a partial-manual shard_map over ``manual_axes`` can run: on
    the installed jax, always. The callers' branches for the other answer
    are ROADMAP D6's to delete, and this function goes with them."""
    return True


def shard_map_manual(fn, mesh, in_specs, out_specs, manual_axes):
    """Partial-manual shard_map: ``manual_axes`` go manual, every other
    mesh axis stays auto (GSPMD)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset(manual_axes), check_vma=False)


def shard_map_compat(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
