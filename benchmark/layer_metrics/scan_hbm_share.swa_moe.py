"""Engine programs, ``swa_gqa_moe`` family: the least bytes a scan iteration
must move (``harness/swa_moe_cost.iteration_bytes``: weights outside the
experts once, the experts TOUCHED, the keys and values each kind's layers had
to attend by the counters, the write) over the HBM peak, as a share of the
iteration's device time (the traced window's ``jit_mega`` + ``jit_mixed`` time
over their iterations).  Tokens, experts touched and positions are the
launches' own (``engine.harvest`` spans).  Prompt chunks and the tiles' padding
make an iteration compute-bound as well; it cannot pass 100."""
from benchmark.harness import swa_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not run.get("peaks"):
        return None
    k = sums["k"]
    glob, window = cost.attended(sums)
    nbytes = cost.iteration_bytes(run["config"], sums["kv_write_tokens"] / k,
                                  sums["experts_touched"] / k, glob / k, window / k)
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / (sums["seconds"] / k)
