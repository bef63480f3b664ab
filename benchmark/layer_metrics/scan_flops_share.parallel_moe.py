"""Engine programs, ``parallel_swa_moe`` family: the least FLOPs of the traced
window's scan launches (``harness/parallel_moe_cost.launch_flops``: attention,
router and shared experts' matmuls on the packed tokens, the routed experts by
the LIVE picks and not by the tiles' rows, the head for the rows sampled,
attention a (row fed, attended position) pair a kind at 4 x 128 x 128 FLOP) over
the bf16 peak, as a share of their device time.  Tokens, picks and positions are
the launches' own (``engine.harvest`` spans); the rows sampled a launch are the
window's (``megastep_tokens`` / ``megasteps``).  It cannot pass 100."""
from benchmark.harness import parallel_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not run.get("peaks") or not (run.get("counters") or {}).get("megasteps"):
        return None
    flops = cost.launch_flops(run["config"], sums["moe_tokens"], sums["moe_local_picks"],
                              cost.sampled_rows(run, sums), *cost.attended(sums))
    return 100.0 * flops / (run["peaks"]["bf16_flops"] * sums["seconds"])
