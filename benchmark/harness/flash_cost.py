"""Operations the flash-attention kernels of one train step must do, from
shapes alone: causal attention, so half the square. A forward kernel does
QK^T and PV; a backward pass needs five such products (the scores again, dP,
dV, dK, dQ) against the forward's two, however the kernels split them, so it
counts 2.5 forwards. A forward that runs again under recompute is work the
step does and counts each time it runs."""

BACKWARD_OVER_FORWARD = 2.5
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def forward_flops(batch, heads, seq, head_dim) -> float:
    return 2.0 * batch * heads * seq * seq * head_dim


def step_flops(cfg, traffic, forward_runs, backward_runs) -> float:
    """``forward_runs`` forward kernels and ``backward_runs`` backward passes
    (one ``flash_bwd_dq`` and one ``flash_bwd_dkv`` each) at the cell's shapes."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    fwd = forward_flops(traffic["batch"], cfg["num_attention_heads"], traffic["seq"], d)
    return fwd * (forward_runs + BACKWARD_OVER_FORWARD * backward_runs)
