"""Train step: model FLOPs per token (matmul weights and causal attention; no
recompute, no embedding gather) x tokens/s of the window, over chips x peak."""
from benchmark.harness import flops


def read(run):
    if not run.get("peaks") or not run.get("tokens_in_window"):
        return None
    per_token = flops.train_flops_per_token(run["config"], run["traffic"]["seq"])
    rate = run["tokens_in_window"] / run["window_s"]
    return 100.0 * per_token * rate / (run["chips"] * run["peaks"]["bf16_flops"])
