"""Engine programs, ``mla_moe`` family: the least FLOPs of a scan launch
(``harness/mla_moe_cost.launch_flops``) over the bf16 peak, as a share of the
launch's device time, both as means over the traced window's ``jit_mega`` +
``jit_mixed`` launches.  Tokens and picks are the launches' own
(``engine.harvest`` spans); the rows sampled a launch are the window's
(``megastep_tokens`` / ``megasteps``); decoding rows attend their live
contexts (mean over the window's ticks) once an iteration, and the other
tokens of a launch are prompt tokens, which attend half their prompt on
average (sum n^2 / 2 over sum n of the prompts sent).  It cannot pass 100."""
from benchmark.harness import mla_moe_cost as cost


def read(run):
    means = cost.launch_means(run)
    c = run.get("counters") or {}
    if means is None or not run.get("peaks") or not c.get("megasteps"):
        return None
    cfg = run["config"]
    _, sparse = cost.layer_counts(cfg)
    tokens = means["moe_tokens"] / sparse
    sampled = c["megastep_tokens"] / c["megasteps"]
    attended = ((run.get("live_tokens_mean") or 0.0) * means["k"]
                + max(tokens - sampled, 0.0) * cost.mean_prefill_position(run))
    flops = cost.launch_flops(cfg, tokens, means["moe_local_picks"], sampled, attended)
    return 100.0 * flops / (run["peaks"]["bf16_flops"] * means["seconds"])
