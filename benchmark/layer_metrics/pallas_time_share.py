"""Kernels: device time of the custom calls inside ``jit_step`` over the time
of ``jit_step`` itself. The default train path runs no Pallas kernel but
flash attention (forward, its recomputation, and two backward kernels), and
the trace names a custom call by the transform it was traced under, not by
the kernel: until the kernels carry names, this share stands in for
``flash_roofline``."""
from benchmark.harness import xplane


def read(run):
    if run.get("trace") is None:
        return None
    step = sum(xplane.module_durations(run["trace"], ("jit_step",)))
    if step <= 0:
        return None
    return 100.0 * xplane.op_seconds(run["trace"], xplane.is_custom_call) / step
