"""Expert layer: rows each held expert multiplies in a scan iteration:
``moe_local_picks`` (picks that fell on an expert held here, read from the
``engine.harvest`` spans of the traced window's launches) over (expert
layers x experts held x scan iterations).  In the deployment the other
chips' tokens would arrive too, so this is 1/16 of a deployed expert's rows
when 16 chips share a layer."""
from benchmark.harness import mla_moe_cost as cost


def read(run):
    means = cost.launch_means(run)
    if means is None:
        return None
    cfg = run["config"]
    _, sparse = cost.layer_counts(cfg)
    return means["moe_local_picks"] / (sparse * cfg["n_routed_experts"] * means["k"])
