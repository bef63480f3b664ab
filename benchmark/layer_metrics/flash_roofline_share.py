"""Kernels: the FLOPs the flash kernels of one step must do (causal half;
a forward counted each time it runs, a backward pass 2.5 forwards:
``harness/flash_cost.py``) over the bf16 peak, as a share of the time they
took (``step_flash_ms``). Compute-bound at these shapes: the kernels read a
few hundred MB a step, 1 ms at the HBM peak."""
from benchmark.harness import flash_cost, program_trace


def read(run):
    got = program_trace.flash_per_step(run)
    if got is None or not run.get("peaks") or got[0] <= 0:
        return None
    seconds, forward_runs, backward_runs = got
    least_s = (flash_cost.step_flops(run["config"], run["traffic"], forward_runs, backward_runs)
               / run["peaks"]["bf16_flops"])
    return 100.0 * least_s / seconds
