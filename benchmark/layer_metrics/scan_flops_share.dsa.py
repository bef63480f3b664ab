"""Engine programs, ``mla_dsa_moe`` family: the least FLOPs of the traced
window's scan launches (``harness/mla_dsa_cost.launch_flops``: matmuls on the
packed tokens with the picks that fell on a held expert, the head for the
rows sampled, the indexer a scored position, the absorbed attention a
selected one) over the bf16 peak, as a share of their device time.  Tokens,
picks, scored and selected positions are the launches' own (``engine.harvest``
spans); the rows sampled a launch are the window's (``megastep_tokens`` /
``megasteps``).  It cannot pass 100."""
from benchmark.harness import mla_dsa_cost as cost
from benchmark.harness import mla_moe_cost


def read(run):
    sums = cost.scan_sums(run)
    c = run.get("counters") or {}
    if sums is None or not run.get("peaks") or not c.get("megasteps"):
        return None
    cfg = run["config"]
    _, sparse = mla_moe_cost.layer_counts(cfg)
    sampled = sums["launches"] * c["megastep_tokens"] / c["megasteps"]
    flops = cost.launch_flops(cfg, sums["moe_tokens"] / sparse, sums["moe_local_picks"],
                              sampled, sums["dsa_positions_scored"],
                              sums["dsa_positions_selected"])
    return 100.0 * flops / (run["peaks"]["bf16_flops"] * sums["seconds"])
