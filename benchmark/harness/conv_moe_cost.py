"""The ``conv_gqa_moe`` family's operations and bytes (gated short
convolutions with state a slot, grouped-query attention every fourth layer,
sigmoid experts with no shared one: LFM2-24B-A2B), from shapes alone, and the
counts a traced run of it carries.  The least a correct implementation does:
every matmul weight outside the routed experts once, an expert's weights once
if a token TOUCHED it (not because it is held), the keys and values of the
live positions once, the state of the rows fed read and written, no padding
of a tile; so a share computed from them cannot pass 100.

``cfg`` is a configuration file's dict (the published key names;
``layer_types`` whole, of which the first ``num_hidden_layers`` are built;
``num_experts`` the router's width, ``experts_held`` the share held, all of
them where the file states none)."""
from benchmark.harness import looped_cost, program_trace

SCANS = ("jit_mega", "jit_mixed")
COUNTS = ("conv_rows_fed", "moe_tokens", "moe_local_picks", "experts_touched",
          "expert_tile_rows", "expert_tile_rows_live", "attn_positions_live",
          "kv_write_tokens")


# ------------------------------------------------------------- from shapes
def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_counts(cfg) -> dict:
    """Layers by kind: {"conv", "attention", "dense", "sparse"} (an operator
    and a feed-forward each, so the two pairs each add up to the depth)."""
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    dense = min(cfg["num_dense_layers"], len(kinds))
    return {"conv": kinds.count("conv"), "attention": kinds.count("full_attention"),
            "dense": dense, "sparse": len(kinds) - dense}


def held_range(cfg):
    """[lo, hi) of the router's experts that the weights hold: all of them
    where the file states no share."""
    lo, hi = cfg.get("experts_held", (0, cfg["num_experts"]))
    return int(lo), int(hi)


def experts_held(cfg) -> int:
    lo, hi = held_range(cfg)
    return hi - lo


def conv_params(cfg) -> int:
    """in_proj, out_proj and the taps of one conv operator."""
    e = cfg["hidden_size"]
    return e * 3 * e + e * e + e * cfg["conv_L_cache"]


def attention_params(cfg) -> int:
    """q, k, v, o of one attention operator."""
    e, d = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return e * h + 2 * e * kv + h * e


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def trunk_params(cfg) -> int:
    """Matmul weights every token passes, outside the routed experts and the
    head: the operators, the dense layers' SwiGLU, the routers."""
    n, e = layer_counts(cfg), cfg["hidden_size"]
    return (n["conv"] * conv_params(cfg) + n["attention"] * attention_params(cfg)
            + n["dense"] * 3 * e * cfg["intermediate_size"]
            + n["sparse"] * e * cfg["num_experts"])


def parameters(cfg) -> dict:
    """Parameters by part (norm gains and the router's bias with their layers)
    and the total; a tied head is the embedding and counts once."""
    n, e = layer_counts(cfg), cfg["hidden_size"]
    d = head_dim(cfg)
    out = {"embed": cfg["vocab_size"] * e,
           "head": 0 if cfg.get("tie_word_embeddings", True) else head_params(cfg),
           "trunk": trunk_params(cfg),
           "experts": n["sparse"] * experts_held(cfg) * expert_params(cfg),
           "gains_and_bias": ((n["conv"] + n["attention"]) * 2 * e + e
                              + n["attention"] * 2 * d + n["sparse"] * cfg["num_experts"])}
    out["total"] = sum(out.values())
    return out


def cache_bytes_per_token(cfg, itemsize=2) -> int:
    """Keys and values of one position in every ATTENTION layer."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize
            * layer_counts(cfg)["attention"])


def state_bytes_per_slot(cfg, itemsize=2) -> int:
    """The conv layers' state of one slot: ``conv_L_cache - 1`` inputs a layer."""
    return ((cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize
            * layer_counts(cfg)["conv"])


def iteration_bytes(cfg, tokens, experts_touched, live_context_tokens, conv_rows_fed,
                    itemsize=2) -> float:
    """HBM bytes one scan iteration over ``tokens`` packed tokens must move:
    every matmul weight outside the routed experts and the head once, the
    weights of the ``experts_touched`` (summed over the expert layers), the
    keys and values of the live positions (the context of every row fed) and
    the tokens' own written, and the state of the ``conv_rows_fed`` (row,
    conv layer) pairs read and written back."""
    state = (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize
    return ((trunk_params(cfg) + head_params(cfg)) * itemsize
            + experts_touched * expert_params(cfg) * itemsize
            + (live_context_tokens + tokens) * cache_bytes_per_token(cfg, itemsize)
            + 2 * conv_rows_fed * state)


def launch_flops(cfg, trunk_tokens, local_picks, sampled_rows, attended_positions) -> float:
    """FLOPs of one launch: 2 a matmul weight a token (the taps' 2 x 3 a
    channel with them), the routed experts by the picks that fell on a held
    one, the head for the rows sampled, QK^T and PV by (row fed, context
    position) pairs in every attention layer (a chunk's own triangle is left
    out: the least)."""
    n = layer_counts(cfg)
    attend = 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * n["attention"]
    return (2.0 * trunk_params(cfg) * trunk_tokens
            + 2.0 * expert_params(cfg) * local_picks
            + 2.0 * head_params(cfg) * sampled_rows
            + attend * attended_positions)


# ------------------------------------------------- what a traced run carries
def scan_sums(run):
    """Over the traced window's scan launches whose ``engine.harvest`` spans
    carry this family's counts: {"launches", "k" (iterations, summed),
    "seconds" (device time of their ``jit_mega`` + ``jit_mixed`` module
    events), and each of ``COUNTS`` summed}; None without a trace, such a
    launch, or the counts (a program without state a slot)."""
    got = looped_cost.launches(run)
    if got is None:
        return None
    trace = program_trace.of(run)
    scans = [l for l in got if l["kind"] in ("mega", "mixed")
             and "conv_rows_fed" in l["counts"]]
    # a module event belongs to the launch whose spans hold its MIDDLE: the
    # device's clock runs 0.6-0.8 ms ahead of the host's on the chip, so since
    # a launch dispatches within a millisecond of its span's start (PR 35) a
    # scan's event STARTS before its ``engine.launch`` span does
    durs = [(b - a) / 1e9 for a, b in program_trace.modules_in(trace, SCANS)
            if any(l["t0"] <= (a + b) // 2 < l["t1"] for l in scans)]
    if not scans or not durs or not sum(l["k"] for l in scans):
        return None
    out = {"launches": len(scans), "k": sum(l["k"] for l in scans), "seconds": sum(durs)}
    out.update({c: sum(l["counts"].get(c, 0) for l in scans) for c in COUNTS})
    return out
