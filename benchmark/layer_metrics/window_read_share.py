"""KV cache pool, ``swa_gqa_moe`` family: the cache positions ONE window layer's
passes brought (``attn_positions_read.window``: a one-token row's blocks from
its window's first to its own, a chunk row's passes from the one that holds its
first key) as a share of the context its rows had (``attn_positions_live.window``),
over the traced window's scan launches (``engine.harvest`` spans).  A global
layer reads 100 and more (its passes round up); what is under 100 here is what
the window spared."""
from benchmark.harness import swa_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not sums["attn_positions_live.window"]:
        return None
    return 100.0 * sums["attn_positions_read.window"] / sums["attn_positions_live.window"]
