"""Expert layer, ``conv_gqa_moe`` family: rows each held expert multiplies in
a scan iteration: ``moe_local_picks`` (off the ``engine.harvest`` spans of the
traced window's scan launches) over (expert layers x experts held x scan
iterations).  What ``expert_rows_per_iteration.py`` counts for the ``mla_moe``
families, by this family's configuration keys.  Every expert of a layer is
held here, so these are a deployed expert's rows."""
from benchmark.harness import conv_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None:
        return None
    cfg = run["config"]
    return sums["moe_local_picks"] / (
        cost.layer_counts(cfg)["sparse"] * cost.experts_held(cfg) * sums["k"])
