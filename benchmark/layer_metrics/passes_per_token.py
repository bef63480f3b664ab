"""Looped trunk: passes of the layers a token ran, ``loop_token_passes`` over
``loop_tokens`` off the ``engine.harvest`` spans of the traced window's scan
launches.  ``total_ut_steps`` (4.0) while every token runs every pass, as the
published threshold of 1 asks; anything less is mathematics left out."""
from benchmark.harness import looped_cost as cost


def read(run):
    means = cost.scan_means(run)
    if means is None or not means["loop_tokens"]:
        return None
    return means["loop_token_passes"] / means["loop_tokens"]
