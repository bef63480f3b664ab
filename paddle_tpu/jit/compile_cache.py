"""Where compiled programs are kept between processes.

Every chip call starts cold unless JAX's persistent compilation cache is
hit, and the directory is part of the cache key: one that moves never hits.
So there is one rule, for every entry point and for the tests' worker
processes: ``JAX_COMPILATION_CACHE_DIR`` where it is set, else one fixed
directory in the checkout.
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Call before the first compile; returns the directory in use. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax read it at import and nothing
    is set here.

    Also keeps Python tracebacks out of MLIR locations. jax strips them from
    a program before it hashes it, but not from the serialized body of a
    Pallas kernel inside it, so the key of every program with a kernel in it
    would change with the checkout's path and with any line that moves in any
    caller (seen on the chip: the train step missed the cache after an edit
    forty lines above it, while the kernel-free serving programs hit)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    if "JAX_TRACEBACK_IN_LOCATIONS_LIMIT" not in os.environ:
        jax.config.update("jax_traceback_in_locations_limit", 0)
    return jax.config.jax_compilation_cache_dir
