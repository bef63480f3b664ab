"""Learned sparse attention: latent entries the attention pass BROUGHT for a
query over those the query may attend, ``dsa_positions_read`` /
``dsa_positions_selected`` off the ``engine.harvest`` spans of the traced
window's scan launches.  1.0 is a pass that reads what it attends; the blocked
dense pass with a mask reads every live entry, context / 2,048."""
from benchmark.harness import mla_dsa_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not sums["dsa_positions_selected"]:
        return None
    return sums["dsa_positions_read"] / sums["dsa_positions_selected"]
