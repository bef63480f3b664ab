"""Engine programs: the bytes a scan iteration must read (every matmul weight
once, plus the keys and values of the live contexts, mean over the window's
ticks) over the HBM peak, as a share of ``scan_iter_ms``. The least bytes of
a decode iteration, so it cannot pass 100; a mixed iteration also prefills,
which makes it lower still."""
from benchmark.harness import flops, loader


def read(run):
    iter_ms = loader.load_module("layer_metrics", "scan_iter_ms").read(run)
    if iter_ms is None or not run.get("peaks"):
        return None
    nbytes = flops.decode_bytes_per_iteration(run["config"], run["live_tokens_mean"])
    least_s = nbytes / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (iter_ms / 1000.0)
