"""Expert layer, ``parallel_swa_moe`` family: rows each HELD routed expert
multiplies in a scan iteration: ``moe_local_picks`` (off the ``engine.harvest``
spans of the traced window's scan launches) over (layers x experts held x scan
iterations).  What ``expert_rows_per_iteration.swa_moe.py`` counts for its
family, by this family's configuration keys.  16 of 128 experts are held, one
chip's share of eight: the deployment's eight batches give an expert 8 x these
rows."""
from benchmark.harness import parallel_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None:
        return None
    cfg = run["config"]
    return sums["moe_local_picks"] / (
        cfg["num_hidden_layers"] * cost.experts_held(cfg) * sums["k"])
