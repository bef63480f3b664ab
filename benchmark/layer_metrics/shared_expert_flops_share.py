"""Expert layer, ``parallel_swa_moe`` family: the shared experts' FLOPs
(``moe_tokens``, the (token, layer) pairs the expert layers saw: every one
passes the shared experts; at 2 FLOP a weight of the four) as a share of the least FLOPs of the traced window's scan launches
(``harness/parallel_moe_cost.launch_flops``), in %.  Every live token passes the
shared experts and an eighth of its picks a held routed expert here, so this is
what the cut makes of their published 27 % of a token's active parameters."""
from benchmark.harness import parallel_moe_cost as cost


def read(run):
    sums = cost.scan_sums(run)
    if sums is None:
        return None
    cfg = run["config"]
    flops = cost.launch_flops(cfg, sums["moe_tokens"], sums["moe_local_picks"],
                              cost.sampled_rows(run, sums), *cost.attended(sums))
    return 100.0 * cost.shared_flops(cfg, sums["moe_tokens"]) / flops if flops else None
