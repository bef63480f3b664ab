"""The ``mla_dsa_moe`` family's operations and bytes (latent attention under
a learned selection: DeepSeek-V3.2-Exp), from shapes alone, and the counts a
traced run of it carries.  The least a correct implementation does: weights
once, the indexer's key of every live position once a row, the latent entries
a query SELECTED and no other, an expert no token picked not read; so a share
computed from them cannot pass 100.  What the family shares with ``mla_moe``
(the latent attention's and the experts' weights, the configuration file's
conventions) is ``harness/mla_moe_cost.py``'s."""
from benchmark.harness import looped_cost, mla_moe_cost, program_trace

SCANS = mla_moe_cost.SCANS
COUNTS = ("dsa_queries", "dsa_positions_scored", "dsa_positions_selected",
          "dsa_positions_read", "attn_positions_live", "moe_tokens", "moe_local_picks")


# ------------------------------------------------------------- from shapes
def index_params(cfg) -> int:
    """Matmul weights of one layer's indexer: queries, key, head weights."""
    e, j, d = cfg["hidden_size"], cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * j * d + e * d + e * j


def trunk_params(cfg) -> int:
    """Matmul weights every token passes, outside the routed experts and the
    head."""
    return mla_moe_cost.trunk_params(cfg) + cfg["num_hidden_layers"] * index_params(cfg)


def entry_values(cfg) -> int:
    """Values a token and layer keeps: the latent, the rope key, the index key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + cfg["index_head_dim"]


def iteration_bytes(cfg, tokens, live_context_tokens, selected_positions, itemsize=2) -> float:
    """HBM bytes one scan iteration over ``tokens`` packed tokens must move:
    every matmul weight outside the routed experts once, a held expert once
    if a token picked it, the index key of every live position (the context
    of every row fed, prefilling rows too: the trunk's ``attn_positions_live``),
    the latent entries the queries selected (no more than are live: a row's
    queries can share what they bring), and the tokens' own entries written."""
    _, sparse = mla_moe_cost.layer_counts(cfg)
    layers = cfg["num_hidden_layers"]
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return ((trunk_params(cfg) + mla_moe_cost.head_params(cfg)) * itemsize
            + sparse * mla_moe_cost.held_experts_hit(cfg, tokens)
            * mla_moe_cost.expert_params(cfg) * itemsize
            + live_context_tokens * cfg["index_head_dim"] * itemsize * layers
            + min(selected_positions, live_context_tokens) * latent * itemsize * layers
            + tokens * entry_values(cfg) * itemsize * layers)


def launch_flops(cfg, trunk_tokens, local_picks, sampled_rows, scored, selected) -> float:
    """FLOPs of one launch: 2 a matmul weight a token, the routed experts by
    the picks that fell on a held one, the head for the rows sampled, the
    indexer's ``2 x heads x head_dim`` a (query, position) scored and the
    absorbed attention's ``2 x heads x (latent + rope + latent)`` a (query,
    position) selected, in every layer.  ``scored`` and ``selected`` are ONE
    layer's, over the queries whose context exceeds ``index_topk`` (the
    engine's counters): what the shorter contexts add is left out."""
    layers = cfg["num_hidden_layers"]
    index = 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
    attend = 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return (2.0 * trunk_params(cfg) * trunk_tokens
            + 2.0 * mla_moe_cost.expert_params(cfg) * local_picks
            + 2.0 * mla_moe_cost.head_params(cfg) * sampled_rows
            + layers * (index * scored + attend * selected))


# ------------------------------------------------- what a traced run carries
def scan_sums(run):
    """Over the traced window's scan launches whose ``engine.harvest`` spans
    carry the selection's counts: {"launches", "k" (iterations, summed),
    "seconds" (device time of their ``jit_mega`` + ``jit_mixed`` module
    events), and each of ``COUNTS`` summed}; None without a trace, such a
    launch, or the counts (a program without an indexer)."""
    got = looped_cost.launches(run)
    if got is None:
        return None
    trace = program_trace.of(run)
    scans = [l for l in got if l["kind"] in ("mega", "mixed") and "dsa_queries" in l["counts"]]
    durs = [(b - a) / 1e9 for a, b in program_trace.modules_in(trace, SCANS)
            if any(l["t0"] <= a < l["t1"] for l in scans)]
    if not scans or not durs or not sum(l["k"] for l in scans):
        return None
    out = {"launches": len(scans), "k": sum(l["k"] for l in scans), "seconds": sum(durs)}
    out.update({c: sum(l["counts"].get(c, 0) for l in scans) for c in COUNTS})
    return out
