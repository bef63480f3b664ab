"""Engine programs, ``mla_dsa_moe`` family: the least bytes a scan iteration
must move (``harness/mla_dsa_cost.iteration_bytes``: weights once, a held
expert if picked, the index key of every live position, the latent entries
the queries selected, the write) over the HBM peak, as a share of the
iteration's device time (the traced window's ``jit_mega`` + ``jit_mixed``
time over their iterations).  Tokens, selected positions and the live context
of the rows fed an iteration are the launches' own (``engine.harvest`` spans).
Prompt chunks make an iteration compute-bound as well; it cannot pass 100."""
from benchmark.harness import mla_dsa_cost as cost
from benchmark.harness import mla_moe_cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not run.get("peaks"):
        return None
    cfg = run["config"]
    _, sparse = mla_moe_cost.layer_counts(cfg)
    k = sums["k"]
    nbytes = cost.iteration_bytes(cfg, sums["moe_tokens"] / (sparse * k),
                                  sums["attn_positions_live"] / k,
                                  sums["dsa_positions_selected"] / k)
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / (sums["seconds"] / k)
