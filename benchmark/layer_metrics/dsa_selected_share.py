"""Learned sparse attention: the context positions the indexer SELECTED over
those it scored, ``dsa_positions_selected`` / ``dsa_positions_scored`` off the
``engine.harvest`` spans of the traced window's scan launches (one layer's,
over the queries whose context exceeds ``index_topk``), in %.  What share of
a long context the attention is allowed: 2,048 of 4-17 k here."""
from benchmark.harness import mla_dsa_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None or not sums["dsa_positions_scored"]:
        return None
    return 100.0 * sums["dsa_positions_selected"] / sums["dsa_positions_scored"]
