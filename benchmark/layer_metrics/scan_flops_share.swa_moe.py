"""Engine programs, ``swa_gqa_moe`` family: the least FLOPs of the traced
window's scan launches (``harness/swa_moe_cost.launch_flops``: matmuls on the
packed tokens, the experts by the LIVE picks and not by the tiles' rows, the
head for the rows sampled, attention a (row fed, attended position) pair a kind)
over the bf16 peak, as a share of their device time.  Tokens, picks and
positions are the launches' own (``engine.harvest`` spans); the rows sampled a
launch are the window's (``megastep_tokens`` / ``megasteps``).  It cannot pass
100."""
from benchmark.harness import swa_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    c = run.get("counters") or {}
    if sums is None or not run.get("peaks") or not c.get("megasteps"):
        return None
    cfg = run["config"]
    sampled = sums["launches"] * c["megastep_tokens"] / c["megasteps"]
    flops = cost.launch_flops(cfg, sums["moe_tokens"] / cfg["num_hidden_layers"],
                              sums["moe_local_picks"], sampled, *cost.attended(sums))
    return 100.0 * flops / (run["peaks"]["bf16_flops"] * sums["seconds"])
