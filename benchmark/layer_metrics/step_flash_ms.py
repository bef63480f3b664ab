"""Kernels: device time of ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` in one ``jit_step`` (the median step), by the names the
kernels' ``pallas_call`` sites carry."""
from benchmark.harness import program_trace


def read(run):
    got = program_trace.flash_per_step(run)
    return None if got is None else 1000.0 * got[0]
