"""Engine programs, ``parallel_swa_moe`` family: the least bytes a scan iteration
must move (``harness/parallel_moe_cost.iteration_bytes``: attention, router and
shared experts' weights once, the table as the head, the routed experts TOUCHED,
the keys and values each kind's layers had to attend by the counters at 4 KiB a
position a layer, the write) over the HBM peak, as a share of the iteration's
device time (the traced window's ``jit_mega`` + ``jit_mixed`` time over their
iterations).  Tokens, experts touched and positions are the launches' own
(``engine.harvest`` spans).  512 rows of products make an iteration compute-bound
as well; it cannot pass 100."""
from benchmark.harness import parallel_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not run.get("peaks"):
        return None
    k = sums["k"]
    glob, window = cost.attended(sums)
    nbytes = cost.iteration_bytes(run["config"], sums["kv_write_tokens"] / k,
                                  sums["experts_touched"] / k, glob / k, window / k)
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / (sums["seconds"] / k)
