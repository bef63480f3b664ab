"""Expert layer, ``swa_gqa_moe`` family: rows each held expert multiplies in a
scan iteration: ``moe_local_picks`` (off the ``engine.harvest`` spans of the
traced window's scan launches) over (layers x experts held x scan iterations).
What ``expert_rows_per_iteration.conv_moe.py`` counts for its family, by this
family's configuration keys.  Every expert of a layer is held here, so these
are a deployed expert's rows."""
from benchmark.harness import swa_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None:
        return None
    cfg = run["config"]
    return sums["moe_local_picks"] / (
        cfg["num_hidden_layers"] * cost.experts_held(cfg) * sums["k"])
