"""The ``swa_gqa_moe`` family's operations and bytes (grouped-query attention
layers of two kinds, global and sliding-window, every feed-forward ReGLU experts
with no shared one: SmallThinker-21BA3B-Instruct), from shapes alone, and the
counts a traced run of it carries.  The least a correct implementation does:
every matmul weight outside the experts once, an expert's weights once if a
token TOUCHED it (not because it is held), the keys and values a kind's layers
had to attend once (a global layer the live context; a window layer the live
context less what lay behind its rows' first keys), the write, no padding of a
tile; so a share computed from them cannot pass 100.

``cfg`` is a configuration file's dict (the published key names;
``sliding_window_layout`` whole, of which the first ``num_hidden_layers`` are
built; ``moe_num_primary_experts`` the router's width, ``experts_held`` the
share held, all of them where the file states none)."""
from benchmark.harness import looped_cost, program_trace

SCANS = ("jit_mega", "jit_mixed")
KINDS = ("global", "window")
COUNTS = ("moe_tokens", "moe_local_picks", "experts_touched", "expert_tile_rows",
          "expert_tile_rows_live", "kv_write_tokens", "window_positions_spared") + tuple(
              f"attn_positions_{what}.{kind}" for kind in KINDS for what in ("live", "read"))


# ------------------------------------------------------------- from shapes
def layer_counts(cfg) -> dict:
    """Cache layers by kind: {"global", "window"}; they add up to the depth."""
    layout = list(cfg["sliding_window_layout"])[:cfg["num_hidden_layers"]]
    return {"global": layout.count(0), "window": layout.count(1)}


def held_range(cfg):
    lo, hi = cfg.get("experts_held", (0, cfg["moe_num_primary_experts"]))
    return int(lo), int(hi)


def experts_held(cfg) -> int:
    lo, hi = held_range(cfg)
    return hi - lo


def attention_params(cfg) -> int:
    """q, k, v, o of one layer: the heads' width is not the hidden size."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return e * h + 2 * e * kv + h * e


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def trunk_params(cfg) -> int:
    """Matmul weights every token passes, outside the experts and the head:
    every layer's attention and router."""
    return cfg["num_hidden_layers"] * (
        attention_params(cfg) + cfg["hidden_size"] * cfg["moe_num_primary_experts"])


def parameters(cfg) -> dict:
    """Parameters by part (norm gains with their layers) and the total; the
    head is a matrix of its own."""
    n, e = cfg["num_hidden_layers"], cfg["hidden_size"]
    out = {"embed": cfg["vocab_size"] * e, "head": head_params(cfg), "trunk": trunk_params(cfg),
           "experts": n * experts_held(cfg) * expert_params(cfg), "gains": (2 * n + 1) * e}
    out["total"] = sum(out.values())
    return out


def cache_bytes_per_position(cfg, itemsize=2) -> int:
    """Keys and values of one position in ONE cache layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def hold_cap(cfg) -> int:
    """The most window blocks a row holds at once: those that overlap its window
    and one launch's reach (``ServingEngine._kind_hold``)."""
    e = cfg["engine"]
    reach = max(e["token_budget"], e["megastep_k"] * e["block_size"])
    return -(-(cfg["sliding_window_size"] + reach) // e["block_size"]) + 1


def iteration_bytes(cfg, tokens, experts_touched, attended_global, attended_window,
                    itemsize=2) -> float:
    """HBM bytes one scan iteration over ``tokens`` packed tokens must move:
    every matmul weight outside the experts once and the head, the weights of
    the ``experts_touched`` (summed over the layers), the keys and values ONE
    layer of each kind had to attend (times the kind's layers) and the tokens'
    own written in every layer."""
    n = layer_counts(cfg)
    return ((trunk_params(cfg) + head_params(cfg)) * itemsize
            + experts_touched * expert_params(cfg) * itemsize
            + (attended_global * n["global"] + attended_window * n["window"]
               + tokens * cfg["num_hidden_layers"]) * cache_bytes_per_position(cfg, itemsize))


def launch_flops(cfg, trunk_tokens, local_picks, sampled_rows, attended_global,
                 attended_window) -> float:
    """FLOPs of one launch: 2 a matmul weight a token, the experts by the picks
    that fell on a held one, the head for the rows sampled, QK^T and PV by (row
    fed, attended position) pairs of ONE layer of each kind times the kind's
    layers (a chunk's own triangle is left out: the least)."""
    n = layer_counts(cfg)
    attend = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return (2.0 * trunk_params(cfg) * trunk_tokens + 2.0 * expert_params(cfg) * local_picks
            + 2.0 * head_params(cfg) * sampled_rows
            + attend * (n["global"] * attended_global + n["window"] * attended_window))


# ------------------------------------------------- what a traced run carries
def _launches(run):
    got = looped_cost.launches(run)
    if got is None or not any("attn_positions_live.window" in l["counts"] for l in got):
        return None
    return got


def scan_sums(run):
    """Over the traced window's scan launches whose ``engine.harvest`` spans
    carry this family's counts: {"launches", "k" (iterations, summed),
    "seconds" (device time of their ``jit_mega`` + ``jit_mixed`` module events,
    matched by the events' middles as ``conv_moe_cost.scan_sums`` does), and
    each of ``COUNTS`` summed}; None without a trace, such a launch, or the
    counts (a program without kinds of cache layer)."""
    got = _launches(run)
    if got is None:
        return None
    trace = program_trace.of(run)
    scans = [l for l in got if l["kind"] in ("mega", "mixed")
             and "attn_positions_live.window" in l["counts"]]
    durs = [(b - a) / 1e9 for a, b in program_trace.modules_in(trace, SCANS)
            if any(l["t0"] <= (a + b) // 2 < l["t1"] for l in scans)]
    if not scans or not durs or not sum(l["k"] for l in scans):
        return None
    out = {"launches": len(scans), "k": sum(l["k"] for l in scans), "seconds": sum(durs)}
    out.update({c: sum(l["counts"].get(c, 0) for l in scans) for c in COUNTS})
    return out


def attended(sums) -> tuple:
    """(positions ONE global layer, ONE window layer had to attend) of
    ``scan_sums``: the live context; the live context less what lay behind the
    rows' first keys."""
    return (sums["attn_positions_live.global"],
            sums["attn_positions_live.window"] - sums["window_positions_spared"])


def blocks_released(run):
    """(window blocks given back, iterations) over the traced window's launches
    of every kind: a harvest span carries the engine's total as it STARTS, so
    the first span's to the last's is what the launches before the last gave
    back; None without two such launches."""
    got = _launches(run)
    got = [l for l in got or () if "window_blocks_released" in l["counts"]]
    if len(got) < 2:
        return None
    return (got[-1]["counts"]["window_blocks_released"] - got[0]["counts"]["window_blocks_released"],
            sum(max(l["k"], 1) for l in got[:-1]))
