"""Expert layer: the live share of what the tile loop multiplied,
``expert_tile_rows_live`` / ``expert_tile_rows`` off the ``engine.harvest``
spans of the traced window's scan launches, in %: an expert's rows are padded
to whole tiles of 128, so 32 rows an expert fill a quarter of one."""
from benchmark.harness import conv_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not sums["expert_tile_rows"]:
        return None
    return 100.0 * sums["expert_tile_rows_live"] / sums["expert_tile_rows"]
