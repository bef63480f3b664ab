"""The plain reference of the ``parallel_swa_moe`` family (command-a-plus-05-2026,
``model_type`` ``cohere2_moe``): a PARALLEL block whose one LayerNorm feeds
grouped-query attention, four averaged shared experts and 8-of-128 sigmoid-routed
experts, all three added to the residual together; attention layers of two kinds
by the published ``layer_types`` (``sliding_attention``: the last
``sliding_window`` positions under RoPE on interleaved pairs; ``full_attention``:
every position, no position encoding); the head is the embedding table.
Straightforward jax.numpy in float32 under ``highest`` matmul precision, the
whole sequence at once: no cache, no block table, no batching, no kernel,
nothing imported from the program.  The 8-bit rounding, an expert's weight a
token and the cut of a padded sequence are benchmark/references/mla_moe.py's,
the bf16 witness's matmul and rounding benchmark/references/conv_gqa_moe.py's,
letter for letter.

    layer:  u = LN(x);  y = x + Attn(u) + Shared(u) + Routed(u)
    LN:     (x - mean(x)) * rsqrt(var(x) + layer_norm_eps) * g: the mean taken
            out, a gain, no bias
    Attn:   q, k, v a head (128 query heads over 8 key/value heads of 128; no
            bias, no head norms); on a sliding layer rope on q and k, pairs
            (2i, 2i + 1) turned together (``rope_gptj``); softmax(q.k / sqrt(128)) v
            under an explicit [S, S] mask: causal, and on a sliding layer also
            key > query - sliding_window; W_o
    Routed: s = sigmoid(u W_r) over all ``router_outputs``; I = the 8 largest;
            w_i = s_i / sum_{j in I} s_j; sum_{i in I, i held} w_i SwiGLU_i(u):
            EVERY held expert computed for every token and weighted by the picks
            (0 where a token did not pick it)
    Shared: the mean of the ``num_shared_experts`` SwiGLUs, each computed apart
            (expert j is columns [jF, (j + 1)F) of ``sg``, ``su`` and those rows
            of ``sd``)
    head:   LN(h) E^T * logit_scale, E the table's rows held

Departures from the published code, each noted at its line: attention a
key/value head at a time and queries in blocks (memory, not mathematics); an
expert at a time.

It is given the SAME share as the program: the experts ``experts_held`` of the
router's ``router_outputs`` (what the absent ones would add is left out), the
vocabulary's rows the table holds.  It takes the benchmark's weights
(benchmark/families/parallel_swa_moe.make_weights: arrays in the served type,
matrices [in, out]) and up-casts a key/value head's share of a matrix, and an
expert, at a time, so that 4.7 B parameters in bf16, one expert in float32 and a
sequence of 33 k at 128 heads fit the chip together.

``quant=`` puts something else in the reference's place.  Lower precisions:
``"int8"`` every matmul by a weight (the router's too) in W8A8; the WITNESS
``"bf16"``: every matmul by a weight with both sides rounded to bfloat16 and the
keys and values a cache would store rounded too.  Controls OF THE MECHANISM,
each what a plausible faulty program computes: ``"serial_block"`` (the
feed-forward reads ``LN(x + Attn(u))``: the block run as every serial trunk runs
its own), ``"shared_sum"`` (the shared experts summed, not averaged),
``"rope_all"`` (RoPE on the full layers too), ``"window_off"`` (sliding layers
attend everything: the window forgotten, or a given-back block read).  And
``"misplaced"``: the sound logits of the row BEFORE each row asked for, so that
every token picked from them is a token in the wrong place (what a program
hands out whose rows and slots are off by one): what ``max_gap_nats`` has to
catch in ONE token, read from a run and not from arithmetic."""
import functools

import jax
import jax.numpy as jnp

from benchmark.harness import loader

_BASE = loader.load_module("references", "mla_moe")
_CONV = loader.load_module("references", "conv_gqa_moe")
weight_of, _cut = _BASE.weight_of, _BASE._cut
_mm, _stored = _CONV._mm, _CONV._stored

F32 = jnp.float32
Q_BLOCK = 256          # query positions attended at a time
MECHANISM = ("serial_block", "shared_sum", "rope_all", "window_off")   # not precisions
MISPLACED = "misplaced"    # no form of the mathematics: the sound logits, a row too early


def _precision(quant):
    return None if quant in MECHANISM else quant


def held_range(cfg):
    lo, hi = cfg.get("experts_held", (0, cfg["num_experts"]))
    return int(lo), int(hi)


def layer_norm(x, g, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32)


def rope_pairs(x, theta):
    """x: [S, H, D]; positions 0..S-1; the pair (2i, 2i + 1) turned by
    position x theta**(-2i / D) (``rope_gptj``: interleaved)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def swiglu(x, wg, wu, wd, quant=None):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def attention(p, u, *, heads, kv_heads, dim, theta, window, rope, quant=None):
    """Grouped-query attention over one sequence u [S, E] (normed) under an
    explicit mask; ``window``: positions a query attends, its own counted (None:
    all before it); ``rope``: whether q and k are rotated."""
    mq = _precision(quant)
    s, e = u.shape
    g = heads // kv_heads
    pad = (-s) % Q_BLOCK
    kpos = jnp.arange(s)

    def head(args):                                    # a key/value head at a time: memory
        wq, wk, wv, wo = args                          # [E, G D], [E, D], [E, D], [G D, E]
        q = _mm(u, wq, mq).reshape(s, g, dim)
        k = _mm(u, wk, mq).reshape(s, 1, dim)
        v = _mm(u, wv, mq)
        if rope:
            q, k = rope_pairs(q, theta), rope_pairs(k, theta)
        k, v = _stored(k[:, 0], quant), _stored(v, quant)
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, g, dim)

        def block(args):                               # queries in blocks: memory
            qi, at = args
            qpos = at * Q_BLOCK + jnp.arange(Q_BLOCK)
            mask = kpos[None, :] <= qpos[:, None]                      # [Q, S] of the [S, S]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            sc = jnp.einsum("qgd,kd->gqk", qi, k, precision="highest") * dim ** -0.5
            pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", pr, v, precision="highest")

        o = jax.lax.map(block, (qb, jnp.arange(qb.shape[0])))
        return _mm(o.reshape(-1, g * dim)[:s], wo, mq)

    per_head = (jnp.moveaxis(p["wq"].reshape(e, kv_heads, g * dim), 1, 0),
                jnp.moveaxis(p["wk"].reshape(e, kv_heads, dim), 1, 0),
                jnp.moveaxis(p["wv"].reshape(e, kv_heads, dim), 1, 0),
                p["wo"].reshape(kv_heads, g * dim, e))
    out, _ = jax.lax.scan(lambda acc, args: (acc + head(args), None),
                          jnp.zeros((s, e), F32), per_head)
    return out


def route(u, w_router, top_k, quant=None):
    """-> (idx [S, k], w [S, k]): sigmoid scores over every routed expert, the
    ``top_k`` largest, their scores normalised over those."""
    s = jax.nn.sigmoid(_mm(u, w_router, quant))
    sv, idx = jax.lax.top_k(s, top_k)
    return idx, sv / jnp.sum(sv, axis=-1, keepdims=True)


# ------------------------------------------------------- jitted pieces, cached
@functools.lru_cache(maxsize=None)
def _jit_op(eps, quant, top_k, **dims):
    def op(p, x):
        """u = LN(x), Attn(u), what the feed-forward reads, and the picks."""
        u = layer_norm(x, p["ln"], eps)
        attn = attention(p, u, quant=quant, **dims)
        # the feed-forward reads the SAME rows as the attention; ``serial_block``
        # what a trunk would that ran the block as the serial families run theirs
        f = layer_norm(x + attn, p["ln"], eps) if quant == "serial_block" else u
        idx, w = route(f, p["router"], top_k, _precision(quant))
        return attn, f, idx, w
    return jax.jit(op)


@functools.lru_cache(maxsize=None)
def _jit_expert(quant):
    def add(acc, x, weight, wg, wu, wd):
        return acc + weight[:, None] * swiglu(x, wg, wu, wd, quant)
    return jax.jit(add, donate_argnums=(0,))


_weight = jax.jit(weight_of)
_join = jax.jit(lambda x, attn, ffn: x + attn + ffn)


@functools.lru_cache(maxsize=None)
def _jit_head(eps, scale, quant):
    def head(norm_w, embed, x, rows):
        return _mm(layer_norm(x[rows], norm_w, eps), embed.T, quant) * scale
    return jax.jit(head)


_OP = ("ln", "wq", "wk", "wv", "wo", "router")


def layer_forward(p, x, cfg, layer, quant=None, held=None):
    """Decoder layer ``layer`` over one sequence, x [S, E] float32.  ``held``:
    the experts [lo, hi) the weights hold (the configuration's where None)."""
    sliding = cfg["layer_types"][layer] == "sliding_attention"
    dims = dict(heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
                dim=cfg["head_dim"], theta=float(cfg["rope_theta"]),
                window=(int(cfg["sliding_window"]) if sliding and quant != "window_off"
                        else None),
                rope=sliding or quant == "rope_all")
    attn, f, idx, w = _jit_op(cfg["layer_norm_eps"], quant, cfg["num_experts_per_tok"], **dims)(
        {k: p[k] for k in _OP}, x)
    add = _jit_expert(_precision(quant))
    lo, hi = held_range(cfg) if held is None else held
    ffn = jnp.zeros_like(x)
    for e in range(hi - lo):                                      # an expert at a time
        ffn = add(ffn, f, _weight(idx, w, jnp.asarray(lo + e, jnp.int32)),
                  p["eg"][e], p["eu"][e], p["ed"][e])
    n, width = cfg["num_shared_experts"], cfg["intermediate_size"]
    share = jnp.full((x.shape[0],), 1.0 if quant == "shared_sum" else 1.0 / n, F32)
    for j in range(n):                                            # each shared expert apart
        at = slice(j * width, (j + 1) * width)
        ffn = add(ffn, f, share, p["sg"][:, at], p["su"][:, at], p["sd"][at])
    return _join(x, attn, ffn)


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
    if quant == MISPLACED:
        rows, quant = [max(int(r) - 1, 0) for r in rows], None
    x = hidden_states(weights, cfg, _cut(ids, rows), quant)
    return _jit_head(cfg["layer_norm_eps"], float(cfg.get("logit_scale", 1)), _precision(quant))(
        weights["norm"], weights["embed"], x, jnp.asarray(rows, jnp.int32))
