"""The ``parallel_swa_moe`` family's operations and bytes (a PARALLEL block:
grouped-query attention of two kinds, window and global, beside shared experts
run as one wide SwiGLU and sigmoid-routed experts of which a share is held; a
head that is the embedding table: command-a-plus-05-2026), from shapes alone,
and the counts a traced run of it carries.  It is ``swa_moe_cost``'s arithmetic
(the same two kinds of cache layer, the same counters under the same names)
read under this family's configuration keys (``under_swa_keys``), PLUS the
shared experts: their weights once an iteration, 2 FLOP a weight a (token,
layer) pair; and the table counted once, for it is the head.  The least a
correct implementation does, so a share computed from them cannot pass 100.

``cfg`` is a configuration file's dict (the published key names;
``layer_types`` whole, of which the first ``num_hidden_layers`` are built;
``num_experts`` the experts HELD, ``router_outputs`` the router's width,
``vocab_size`` the table's rows held)."""
from benchmark.harness import swa_moe_cost as swa
from benchmark.harness.swa_moe_cost import (  # noqa: F401  the same keys, the same arithmetic
    attended, attention_params, cache_bytes_per_position, head_params)

FAMILY = "parallel_swa_moe"


def router_outputs(cfg) -> int:
    return cfg.get("router_outputs", cfg["num_experts"])


def under_swa_keys(cfg) -> dict:
    """``cfg`` with what ``swa_moe_cost`` reads under the names it reads them by."""
    return dict(
        cfg, sliding_window_layout=[int(t == "sliding_attention") for t in cfg["layer_types"]],
        sliding_window_size=cfg["sliding_window"], moe_num_primary_experts=router_outputs(cfg),
        moe_ffn_hidden_size=cfg["intermediate_size"],
        experts_held=cfg.get("experts_held", (0, cfg["num_experts"])))


def _of_swa(name):
    def through(cfg, *args, **kw):
        return getattr(swa, name)(under_swa_keys(cfg), *args, **kw)
    through.__doc__ = getattr(swa, name).__doc__
    return through


layer_counts, experts_held, expert_params, hold_cap = (
    _of_swa(n) for n in ("layer_counts", "experts_held", "expert_params", "hold_cap"))


# ------------------------------------------------------------- from shapes
def shared_params(cfg) -> int:
    """The shared experts of one layer, each an expert's width."""
    return cfg["num_shared_experts"] * expert_params(cfg)


def trunk_params(cfg) -> int:
    """Matmul weights every token passes, outside the routed experts and the
    head: every layer's attention, router and shared experts."""
    return swa.trunk_params(under_swa_keys(cfg)) + cfg["num_hidden_layers"] * shared_params(cfg)


def parameters(cfg) -> dict:
    """Parameters by part (norm gains with their layers) and the total; the
    table is counted ONCE (it is the head)."""
    n, e = cfg["num_hidden_layers"], cfg["hidden_size"]
    out = {"embed": head_params(cfg), "trunk": trunk_params(cfg),
           "experts": n * experts_held(cfg) * expert_params(cfg), "gains": (n + 1) * e}
    out["total"] = sum(out.values())
    return out


def iteration_bytes(cfg, tokens, experts_touched, attended_global, attended_window,
                    itemsize=2) -> float:
    """``swa_moe_cost.iteration_bytes`` and every layer's shared experts once."""
    return (swa.iteration_bytes(under_swa_keys(cfg), tokens, experts_touched, attended_global,
                                attended_window, itemsize)
            + cfg["num_hidden_layers"] * shared_params(cfg) * itemsize)


def shared_flops(cfg, layer_tokens) -> float:
    """The shared experts' FLOPs over ``layer_tokens`` (token, layer) pairs."""
    return 2.0 * shared_params(cfg) * layer_tokens


def launch_flops(cfg, layer_tokens, local_picks, sampled_rows, attended_global,
                 attended_window) -> float:
    """``swa_moe_cost.launch_flops`` over ``layer_tokens`` (token, layer) pairs
    (``moe_tokens``: every live token a layer) and the shared experts' on them."""
    return (swa.launch_flops(under_swa_keys(cfg), layer_tokens / cfg["num_hidden_layers"],
                             local_picks, sampled_rows, attended_global, attended_window)
            + shared_flops(cfg, layer_tokens))


# ------------------------------------------------- what a traced run carries
def scan_sums(run):
    """``swa_moe_cost.scan_sums`` of a run of THIS family's configuration (its
    trunk seeds the same counters under the same names); None for another
    family's, and where that gives None."""
    if (run.get("config") or {}).get("family") != FAMILY:
        return None
    return swa.scan_sums(run)


def sampled_rows(run, sums) -> float:
    """Rows the traced scans headed: the window's ``megastep_tokens`` a launch."""
    c = run.get("counters") or {}
    return sums["launches"] * c["megastep_tokens"] / c["megasteps"] if c.get("megasteps") else 0.0
