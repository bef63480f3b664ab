"""Engine programs, ``conv_gqa_moe`` family: the least bytes a scan iteration
must move (``harness/conv_moe_cost.iteration_bytes``: weights outside the
experts once, the experts TOUCHED, the keys and values of the live positions,
the state of the rows fed, the write) over the HBM peak, as a share of the
iteration's device time (the traced window's ``jit_mega`` + ``jit_mixed`` time
over their iterations).  Tokens, experts touched, live context and rows fed
are the launches' own (``engine.harvest`` spans).  Prompt chunks and the
tiles' padding make an iteration compute-bound as well; it cannot pass 100."""
from benchmark.harness import conv_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not run.get("peaks"):
        return None
    cfg = run["config"]
    k = sums["k"]
    nbytes = cost.iteration_bytes(cfg, sums["kv_write_tokens"] / k,
                                  sums["experts_touched"] / k,
                                  sums["attn_positions_live"] / k,
                                  sums["conv_rows_fed"] / k)
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / (sums["seconds"] / k)
