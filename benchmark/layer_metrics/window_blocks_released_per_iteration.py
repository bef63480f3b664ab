"""KV cache pool, ``swa_gqa_moe`` family: blocks of the window kind's pool that
running rows gave back behind their windows, an iteration: the engine's
``window_blocks_released`` (monotone, on the ``engine.harvest`` spans) from the
traced window's first launch to its last, over the iterations between.  Every
other cell frees a block when its request ends; here the pool turns over while
the rows run."""
from benchmark.harness import swa_moe_cost as cost


def read(run):
    got = cost.blocks_released(run)
    if got is None or not got[1]:
        return None
    return got[0] / got[1]
