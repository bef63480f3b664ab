"""The plain reference of the ``swa_gqa_moe`` family (SmallThinker-21BA3B-Instruct,
``model_name`` ``smallthinker_21b_instruct``): grouped-query attention layers of
two kinds by a published layout (``sliding_window_layout`` == ``rope_layout``,
period ``0, 1, 1, 1``: 0 a GLOBAL layer with no position encoding, 1 a layer
that attends the last ``sliding_window_size`` positions and has RoPE), every
layer's feed-forward 64 ReGLU experts of which a token takes 6 by a router that
reads the ATTENTION's input.  Straightforward jax.numpy in float32 under
``highest`` matmul precision, the whole sequence at once: no cache, no block
table, no batching, no kernel, nothing imported from the program.  RMSNorm, the
8-bit rounding, an expert's weight a token and the cut of a padded sequence are
benchmark/references/mla_moe.py's, the bf16 witness's matmul and rounding
benchmark/references/conv_gqa_moe.py's, letter for letter.

    layer:  a = n_in(x);  r = a W_r;  h = x + Attn(a);  y = h + MoE(n_post(h); r)
    Attn:   q, k, v a head (28 query heads over 4 key/value heads of 128; no bias,
            no head norms); on a window layer rope (halves rotated) on q and k;
            softmax(q.k / sqrt(128)) v under an explicit [S, S] mask: causal, and
            on a window layer also key > query - sliding_window_size; W_o
    MoE:    I = the 6 largest of r's 64 logits; w = softmax over THOSE;
            MoE = sum_{i in I} w_i W_down_i(relu(W_gate_i u) * (W_up_i u)): EVERY
            expert computed for every token and weighted by the picks (0 where a
            token did not pick it)

Departures from the published code, each noted at its line: attention a key/value
head at a time and queries in blocks (memory, not mathematics); the weights a
softmax over the chosen logits where the published router takes a softmax over
all 64 and renormalises the chosen (the same six numbers); no secondary experts
(the configuration has none).

It takes the benchmark's weights (benchmark/families/swa_gqa_moe.make_weights:
arrays in the served type, matrices [in, out]) and up-casts a layer's matrices,
and an expert at a time, so that 5.56 B parameters in bf16 and one expert in
float32 fit the chip together; the queries go in blocks of ``Q_BLOCK`` so that a
sequence of 16 k fits beside them.

``quant=`` puts something else in the reference's place.  Lower precisions:
``"int8"`` every matmul by a weight (the router's too) in W8A8; the WITNESS
``"bf16"``: every matmul by a weight with both sides rounded to bfloat16 and the
keys and values a cache would store rounded too.  Controls OF THE MECHANISM,
each what a faulty program would compute: ``"window_off"`` (window layers attend
everything: a program that forgot the window, or read blocks it had given back),
``"rope_all"`` (RoPE on the global layers too), ``"router_post"`` (the router
reads ``n_post(h)``, the expert layer's own input)."""
import functools

import jax
import jax.numpy as jnp

from benchmark.harness import loader

_BASE = loader.load_module("references", "mla_moe")
_CONV = loader.load_module("references", "conv_gqa_moe")
_rms, weight_of, _cut = _BASE._rms, _BASE.weight_of, _BASE._cut
_mm, _stored, _rope = _CONV._mm, _CONV._stored, _CONV._rope

F32 = jnp.float32
Q_BLOCK = 256          # query positions attended at a time
MECHANISM = ("window_off", "rope_all", "router_post")   # not precisions


def _precision(quant):
    return None if quant in MECHANISM else quant


def held_range(cfg):
    lo, hi = cfg.get("experts_held", (0, cfg["moe_num_primary_experts"]))
    return int(lo), int(hi)


def layouts(cfg):
    """(window?, rope?) of each layer built: the first ``num_hidden_layers``
    entries of the published lists."""
    n = cfg["num_hidden_layers"]
    return (list(cfg["sliding_window_layout"])[:n], list(cfg["rope_layout"])[:n])


def reglu(x, wg, wu, wd, quant=None):
    return _mm(jax.nn.relu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def attention(p, a, *, heads, kv_heads, dim, theta, window, rope, quant=None):
    """Grouped-query attention over one sequence a [S, E] (normed) under an
    explicit mask; ``window``: positions a query attends, its own counted (None:
    all before it); ``rope``: whether q and k are rotated."""
    mq = _precision(quant)
    s = a.shape[0]
    q = _mm(a, p["wq"], mq).reshape(s, heads, dim)
    k = _mm(a, p["wk"], mq).reshape(s, kv_heads, dim)
    v = _mm(a, p["wv"], mq).reshape(s, kv_heads, dim)
    if rope:
        q, k = _rope(q, theta), _rope(k, theta)
    k, v = _stored(k, quant), _stored(v, quant)
    g = heads // kv_heads
    pad = (-s) % Q_BLOCK
    kpos = jnp.arange(s)

    def head(args):                                    # a key/value head at a time: memory
        qh, kh, vh = args                              # [S, G, D], [S, D], [S, D]
        qb = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, g, dim)

        def block(args):                               # queries in blocks: memory
            qi, at = args
            qpos = at * Q_BLOCK + jnp.arange(Q_BLOCK)
            mask = kpos[None, :] <= qpos[:, None]                      # [Q, S] of the [S, S]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            sc = jnp.einsum("qgd,kd->gqk", qi, kh, precision="highest") * dim ** -0.5
            pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", pr, vh, precision="highest")

        o = jax.lax.map(block, (qb, jnp.arange(qb.shape[0])))
        return o.reshape(-1, g, dim)[:s]

    qg = jnp.moveaxis(q.reshape(s, kv_heads, g, dim), 1, 0)
    o = jax.lax.map(head, (qg, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return _mm(jnp.moveaxis(o, 0, 1).reshape(s, heads * dim), p["wo"], mq)


def route(logits, top_k):
    """-> (idx [S, k], w [S, k]): the ``top_k`` largest logits, a softmax over
    those (the published router's softmax over all, renormalised over the
    chosen, is the same numbers)."""
    gv, idx = jax.lax.top_k(logits, top_k)
    return idx, jax.nn.softmax(gv, axis=-1)


# ------------------------------------------------------- jitted pieces, cached
@functools.lru_cache(maxsize=None)
def _jit_op(eps, quant, top_k, **dims):
    def op(p, x):
        """h = x + Attn(n_in(x)), u = n_post(h), and the picks from the router."""
        a = _rms(x, p["ln_in"], eps)
        h = x + attention(p, a, quant=quant, **dims)
        u = _rms(h, p["ln_post"], eps)
        # the router reads the attention's INPUT; ``router_post`` what a trunk
        # would that routed where the other families do
        idx, w = route(_mm(u if quant == "router_post" else a, p["router"],
                           _precision(quant)), top_k)
        return h, u, idx, w
    return jax.jit(op)


@functools.lru_cache(maxsize=None)
def _jit_expert(quant):
    def add(acc, x, idx, w, expert, wg, wu, wd):
        return acc + weight_of(idx, w, expert)[:, None] * reglu(x, wg, wu, wd, quant)
    return jax.jit(add, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jit_add():
    return jax.jit(lambda h, ffn: h + ffn)


@functools.lru_cache(maxsize=None)
def _jit_head(eps, quant):
    def head(norm_w, head_w, x, rows):
        return _mm(_rms(x[rows], norm_w, eps), head_w, quant)
    return jax.jit(head)


_OP = ("ln_in", "ln_post", "wq", "wk", "wv", "wo", "router")


def layer_forward(p, x, cfg, layer, quant=None):
    """Decoder layer ``layer`` over one sequence, x [S, E] float32."""
    windows, ropes = layouts(cfg)
    windowed = bool(windows[layer]) and quant != "window_off"
    dims = dict(heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
                dim=cfg["head_dim"], theta=float(cfg["rope_theta"]),
                window=int(cfg["sliding_window_size"]) if windowed else None,
                rope=bool(ropes[layer]) or quant == "rope_all")
    h, u, idx, w = _jit_op(cfg["rms_norm_eps"], quant,
                           cfg["moe_num_active_primary_experts"], **dims)(
        {k: p[k] for k in _OP}, x)
    lo, hi = held_range(cfg)
    ffn = jnp.zeros_like(h)
    for e in range(hi - lo):                                      # an expert at a time
        ffn = _jit_expert(_precision(quant))(ffn, u, idx, w, jnp.asarray(lo + e, jnp.int32),
                                             p["eg"][e], p["eu"][e], p["ed"][e])
    return _jit_add()(h, ffn)


def hidden_states(weights, cfg, ids, quant=None):
    """[S, E] float32: the last layer's output, before the final norm."""
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(F32)
    for layer, p in enumerate(weights["layers"]):
        x = layer_forward(p, x, cfg, layer, quant)
    return x


def logits_at(weights, cfg, ids, rows, quant=None, n_prompt=0):
    """Logits [len(rows), V] float32 of the full forward over ``ids`` [S] at
    the positions ``rows``: row r predicts token r + 1.  ``n_prompt`` is part
    of the references' common signature; nothing here reads it."""
    x = hidden_states(weights, cfg, _cut(ids, rows), quant)
    return _jit_head(cfg["rms_norm_eps"], _precision(quant))(
        weights["norm"], weights["head"], x, jnp.asarray(rows, jnp.int32))
