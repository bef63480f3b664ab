"""Expert layer: the held experts that got at least one row in a scan
iteration, ``experts_touched`` (summed over expert layers and iterations, off
the ``engine.harvest`` spans of the traced window's scan launches) over
(expert layers x experts held x iterations), in %: whose weights an iteration
has to read."""
from benchmark.harness import conv_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None:
        return None
    cfg = run["config"]
    return 100.0 * sums["experts_touched"] / (
        cost.layer_counts(cfg)["sparse"] * cost.experts_held(cfg) * sums["k"])
