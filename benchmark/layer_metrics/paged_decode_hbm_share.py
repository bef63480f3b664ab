"""Kernels: ``paged_decode``'s share of its roofline, which is bandwidth: the
cache bytes it had to bring (``attn_positions_read`` of the traced window's
decode-only launches, where every row is one of its rows, x keys and values of
a position x the cache layers: layers x passes) over the HBM peak, as a share
of the device time of the events named ``paged_decode`` in those launches.
The count rounds a row's context up to a block, which is what the kernel
copies; the queries, the output and the table are left out, so it cannot pass
100.  Reads a model of one pass too (the Mistral cells)."""
from benchmark.harness import looped_cost as cost


def read(run):
    got = cost.decode_kernel(run)
    if got is None or not run.get("peaks"):
        return None
    cfg = run["config"]
    nbytes = got["positions_read"] * cost.cache_bytes_per_position(cfg) * cost.cache_layers(cfg)
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / got["seconds"]
