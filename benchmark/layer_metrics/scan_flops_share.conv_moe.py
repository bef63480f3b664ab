"""Engine programs, ``conv_gqa_moe`` family: the least FLOPs of the traced
window's scan launches (``harness/conv_moe_cost.launch_flops``: matmuls on the
packed tokens, the experts by the LIVE picks and not by the tiles' rows, the
head for the rows sampled, attention a (row fed, context position) pair) over
the bf16 peak, as a share of their device time.  Tokens, picks and positions
are the launches' own (``engine.harvest`` spans); the rows sampled a launch are
the window's (``megastep_tokens`` / ``megasteps``).  It cannot pass 100."""
from benchmark.harness import conv_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    c = run.get("counters") or {}
    if sums is None or not run.get("peaks") or not c.get("megasteps"):
        return None
    cfg = run["config"]
    sampled = sums["launches"] * c["megastep_tokens"] / c["megasteps"]
    flops = cost.launch_flops(cfg, sums["moe_tokens"] / cost.layer_counts(cfg)["sparse"],
                              sums["moe_local_picks"], sampled, sums["attn_positions_live"])
    return 100.0 * flops / (run["peaks"]["bf16_flops"] * sums["seconds"])
