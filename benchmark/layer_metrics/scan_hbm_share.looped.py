"""Engine programs, ``looped_dense`` family: the least bytes a scan iteration
must read (``harness/looped_cost.iteration_bytes``: the layers' matmul weights
once a PASS, the head once, the cache of the live contexts at a cache layer a
(pass, layer), mean over the window's ticks) over the HBM peak, as a share of
the iteration's device time (the traced window's ``jit_mega`` + ``jit_mixed``
time over their iterations).  ``scan_hbm_share`` counts each layer once
(``harness/flops.py``) and would read a quarter of this.  Prompt chunks and
192 layers' small operations make it lower; it cannot pass 100."""
from benchmark.harness import looped_cost as cost


def read(run):
    means = cost.scan_means(run)
    if means is None or not run.get("peaks"):
        return None
    nbytes = cost.iteration_bytes(run["config"], run.get("live_tokens_mean") or 0.0)
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / means["iter_s"]
