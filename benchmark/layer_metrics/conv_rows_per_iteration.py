"""State a slot: the rows whose conv state a scan iteration advanced,
``conv_rows_fed`` (row-layers, off the ``engine.harvest`` spans of the traced
window's scan launches) over (conv layers x iterations): decoding rows and
rows that feed a prompt chunk alike, each reading its slot's state and
writing it back."""
from benchmark.harness import conv_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None:
        return None
    return sums["conv_rows_fed"] / (cost.layer_counts(run["config"])["conv"] * sums["k"])
