"""The ``mla_moe`` family's operations and bytes, from shapes alone, and the
counts a traced run of it carries.  The least a correct implementation does:
no padding, no gather of dead positions, an expert no token picked not read;
so a share computed from them cannot pass 100.

``cfg`` is a configuration file's dict (the published key names;
``n_routed_experts`` the experts HELD here, ``router_outputs`` the router's
width)."""
from benchmark.harness import program_trace

SCANS = ("jit_mega", "jit_mixed")


# ------------------------------------------------------------- from shapes
def attention_params(cfg) -> int:
    """Matmul weights of one layer's latent attention."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return e * ql + ql * h * (n + r) + e * (kl + r) + kl * h * (n + v) + h * v * e


def expert_params(cfg) -> int:
    """One expert, routed or shared: SwiGLU hidden -> moe_intermediate -> hidden."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg):
    """(leading dense layers, expert layers)."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def router_outputs(cfg) -> int:
    return cfg.get("router_outputs", cfg["n_routed_experts"])


def trunk_params(cfg) -> int:
    """Matmul weights every token passes, outside the routed experts and the
    head: attention, the dense layers' FFN, routers, shared experts."""
    dense, sparse = layer_counts(cfg)
    e = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * 3 * e * cfg["intermediate_size"]
            + sparse * (e * router_outputs(cfg) + cfg["n_shared_experts"] * expert_params(cfg)))


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def latent_bytes_per_token(cfg, itemsize=2) -> int:
    """One cache entry a layer: the latent and the shared rope key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize * cfg["num_hidden_layers"]


def held_experts_hit(cfg, tokens) -> float:
    """Expected number of an expert layer's held experts that at least one
    of ``tokens`` tokens picks, under uniform routing."""
    miss = (1.0 - cfg["num_experts_per_tok"] / router_outputs(cfg)) ** max(tokens, 0.0)
    return cfg["n_routed_experts"] * (1.0 - miss)


def iteration_bytes(cfg, tokens, live_context_tokens, itemsize=2) -> float:
    """HBM bytes one scan iteration over ``tokens`` packed tokens must read:
    every matmul weight outside the routed experts once, a held expert once
    if a token picked it, and the latent cache of the live contexts."""
    _, sparse = layer_counts(cfg)
    return ((trunk_params(cfg) + head_params(cfg)) * itemsize
            + sparse * held_experts_hit(cfg, tokens) * expert_params(cfg) * itemsize
            + live_context_tokens * latent_bytes_per_token(cfg, itemsize))


def attention_flops_per_position(cfg) -> float:
    """QK^T and PV of one token against one context position, all heads and
    layers, in the non-absorbed count (192 + 128 a head: the smaller)."""
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * d * cfg["num_hidden_layers"]


def launch_flops(cfg, trunk_tokens, local_picks, sampled_rows, attended_positions) -> float:
    """FLOPs of the tokens of one launch: 2 a matmul weight a token, the
    routed experts by the picks that fell on a held one, the head for the
    rows sampled, attention by (token, context position) pairs."""
    return (2.0 * trunk_params(cfg) * trunk_tokens
            + 2.0 * expert_params(cfg) * local_picks
            + 2.0 * head_params(cfg) * sampled_rows
            + attention_flops_per_position(cfg) * attended_positions)


# ------------------------------------------------- what a traced run carries
def launch_means(run):
    """Means over the traced window's scan launches: {"k": iterations,
    "seconds": device time, "moe_tokens", "moe_local_picks"} of one launch;
    None where the run has no trace, no scan launch in it, or its
    ``engine.harvest`` spans carry no expert counts (a dense model, or a
    program from before they were counted)."""
    trace = program_trace.of(run)
    if trace is None:
        return None
    t0, t1 = trace.window
    durs = [(b - a) / 1e9 for a, b in program_trace.modules_in(trace, SCANS)]
    inside = [(name, st) for name, s, d, st in trace.host if s >= t0 and s + d <= t1]
    ks = [int(st["k"]) for name, st in inside
          if name == "engine.launch" and st.get("kind") in ("mega", "mixed")]
    counts = [st for name, st in inside if name == "engine.harvest" and "moe_tokens" in st]
    if not durs or not ks or not counts:
        return None
    return {"k": sum(ks) / len(ks),
            "seconds": sum(durs) / len(durs),
            "moe_tokens": sum(int(st["moe_tokens"]) for st in counts) / len(counts),
            "moe_local_picks": sum(int(st["moe_local_picks"]) for st in counts) / len(counts)}


def mean_prefill_position(run) -> float:
    """Context positions a prompt token attends, on average over the prompt
    tokens of the requests sent: sum n^2 / 2 over sum n."""
    lens = [len(r.prompt) for r in run.get("requests") or []]
    return sum(n * n for n in lens) / (2.0 * sum(lens)) if lens else 0.0
