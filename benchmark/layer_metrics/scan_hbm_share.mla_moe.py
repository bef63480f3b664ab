"""Engine programs, ``mla_moe`` family: the least bytes a scan iteration must
read (``harness/mla_moe_cost.iteration_bytes``: every matmul weight outside
the routed experts once; a held expert's weights times the expected share of
held experts that get a token; the latent cache of the live contexts, mean
over the window's ticks) over the HBM peak, as a share of the iteration's
device time (the traced window's ``jit_mega`` + ``jit_mixed`` time over their
iterations).  Prompt chunks make an iteration compute-bound as well, which
makes this lower still; it cannot pass 100."""
from benchmark.harness import mla_moe_cost as cost


def read(run):
    means = cost.launch_means(run)
    if means is None or not run.get("peaks"):
        return None
    cfg = run["config"]
    _, sparse = cost.layer_counts(cfg)
    tokens = means["moe_tokens"] / (sparse * means["k"])
    nbytes = cost.iteration_bytes(cfg, tokens, run.get("live_tokens_mean") or 0.0)
    least_s = nbytes / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (means["seconds"] / means["k"])
