"""The ``looped_dense`` family's operations and bytes, from shapes alone, and
the counts a traced run of it carries.  A looped model applies ONE stack of
layers ``total_ut_steps`` times to every token, so an iteration reads the
layers' weights once a pass, and keeps a cache layer a (pass, layer).  The
least a correct implementation does: no padding, no dead position read, a
weight read once a pass (16 rows cannot keep 103 MB of a layer in fast
memory from one pass to the next); so a share computed from them cannot pass
100.

``cfg`` is a configuration file's dict (the published key names).  The cache
functions read ``total_ut_steps`` as 1 where a configuration has none, so the
kernel's share reads a model of one pass too."""
from benchmark.harness import program_trace

SCANS = ("jit_mega", "jit_mixed")


# ------------------------------------------------------------- from shapes
def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg) -> int:
    """q, k, v, o and the SwiGLU's three."""
    e, f, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return e * h + 2 * e * kv + h * e + 3 * e * f


def parameters(cfg) -> dict:
    """Parameters by part: a layer (its four norm gains with it), all the
    layers, the embedding, the head, the final norm with the exit gate (its
    bias too), and the total."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    layer = layer_matmul_params(cfg) + 4 * e
    out = {"layer": layer, "layers": cfg["num_hidden_layers"] * layer,
           "embed": v * e, "head": e * v, "norm_and_gate": e + e + 1}
    out["total"] = out["layers"] + out["embed"] + out["head"] + out["norm_and_gate"]
    return out


def passes(cfg) -> int:
    return int(cfg.get("total_ut_steps", 1))


def cache_layers(cfg) -> int:
    """A cache layer a (pass, layer)."""
    return passes(cfg) * cfg["num_hidden_layers"]


def cache_bytes_per_position(cfg, itemsize=2) -> int:
    """Keys and values of one position in ONE cache layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def cache_bytes_per_token(cfg, itemsize=2) -> int:
    return cache_bytes_per_position(cfg, itemsize) * cache_layers(cfg)


def iteration_bytes(cfg, live_context_tokens, itemsize=2) -> float:
    """HBM bytes one scan iteration must read: the layers' matmul weights
    once a pass, the head once, the cache of the live contexts."""
    return (passes(cfg) * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * itemsize
            + cfg["hidden_size"] * cfg["vocab_size"] * itemsize
            + live_context_tokens * cache_bytes_per_token(cfg, itemsize))


def launch_flops(cfg, tokens, sampled_rows, attended_positions) -> float:
    """FLOPs of one launch: 2 a matmul weight a token a pass, the head for
    the rows sampled, QK^T and PV by (token, context position) pairs of ONE
    cache layer times the cache layers."""
    attn = 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * cache_layers(cfg)
    return (2.0 * passes(cfg) * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * tokens
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * sampled_rows
            + attn * attended_positions)


# ------------------------------------------------- what a traced run carries
def launches(run):
    """The engine's launches that lie whole inside the traced window, in
    order: [{"kind", "k", "t0", "t1" (ns, from the launch span's start to its
    harvest span's end), "counts": the harvest span's stats as ints}]; None
    where the run has no trace or its trace no such pair."""
    trace = program_trace.of(run)
    if trace is None:
        return None
    w0, w1 = trace.window
    out, open_launch = [], None
    for name, s, d, st in trace.host:
        if name == "engine.launch":
            open_launch = {"kind": st.get("kind"), "k": int(st.get("k", 0)), "t0": s}
        elif name == "engine.harvest" and open_launch is not None:
            if open_launch["t0"] >= w0 and s + d <= w1:
                counts = {k: int(v) for k, v in st.items()
                          if str(v).lstrip("-").isdigit()}
                out.append(dict(open_launch, t1=s + d, counts=counts))
            open_launch = None
    return out or None


def scan_means(run):
    """Over the traced window's scan launches: {"iter_s": device seconds of
    the ``jit_mega`` + ``jit_mixed`` module events over their iterations,
    "loop_tokens", "loop_token_passes": sums over their harvest spans}; None
    without a trace, a scan launch, or the loop's counts (a model of one
    pass, or a program from before they were counted)."""
    got = launches(run)
    if got is None:
        return None
    trace = program_trace.of(run)
    scans = [l for l in got if l["kind"] in ("mega", "mixed") and "loop_tokens" in l["counts"]]
    durs = [(b - a) / 1e9 for a, b in program_trace.modules_in(trace, SCANS)
            if any(l["t0"] <= a < l["t1"] for l in scans)]
    ks = sum(l["k"] for l in scans)
    if not scans or not durs or not ks:
        return None
    return {"iter_s": sum(durs) / ks,
            "loop_tokens": sum(l["counts"]["loop_tokens"] for l in scans),
            "loop_token_passes": sum(l["counts"]["loop_token_passes"] for l in scans)}


def decode_kernel(run, kernel="paged_decode"):
    """Over the traced window's DECODE-ONLY scan launches (every row feeds one
    token, so what the attention read is what the kernel read): {"seconds":
    device time of the kernel's events inside their ``jit_mega`` module
    events, "positions_read": ``attn_positions_read`` of their harvest spans
    (one cache layer's), "rows": ``attn_rows_kernel``}; None without a trace,
    such a launch, the counts, or a kernel event of that name (the XLA pass)."""
    got = launches(run)
    if got is None:
        return None
    trace = program_trace.of(run)
    megas = [l for l in got if l["kind"] == "mega"
             and l["counts"].get("attn_rows_kernel", 0) > 0]
    seconds = 0.0
    for (a, _), per in zip(program_trace.modules_in(trace, ("jit_mega",)),
                           program_trace.kernel_seconds(trace, (kernel,), ("jit_mega",))):
        if kernel in per and any(l["t0"] <= a < l["t1"] for l in megas):
            seconds += per[kernel][1]
    if not megas or not seconds:
        return None
    return {"seconds": seconds,
            "positions_read": sum(l["counts"]["attn_positions_read"] for l in megas),
            "rows": sum(l["counts"]["attn_rows_kernel"] for l in megas)}
