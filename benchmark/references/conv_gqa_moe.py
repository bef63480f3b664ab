"""The plain reference of the ``conv_gqa_moe`` family (LFM2-24B-A2B,
``model_type`` ``lfm2_moe``): gated short convolutions with a grouped-query
attention layer every fourth, two leading dense SwiGLU layers, then
sigmoid-routed experts chosen on score + bias, no shared expert.
Straightforward jax.numpy in float32 under ``highest`` matmul precision, whole
sequences: no cache, no state, no batching, no kernel, nothing imported from
the program.  RMSNorm, the 8-bit rounding, an expert's weight a token and the
cut of a padded sequence are benchmark/references/mla_moe.py's, letter for
letter.

    layer:  h = x + op(n_operator(x));  y = h + ffn(n_ffn(h));  n before the head
    conv:   [B, C, u] = split3(x W_in);  v = B * u;
            c_t = k[:, 0] v_{t-2} + k[:, 1] v_{t-1} + k[:, 2] v_t  (zeros before
            position 0: the sequence shifted by two and by one);  (C * c) W_out
    attn:   q, k, v a head; RMSNorm over each query and key head; rope (halves
            rotated) on q and k; causal softmax(q.k / sqrt(head_dim)) v under a
            full mask, a key/value head shared by a group of query heads; W_o
    expert: s = sigmoid(x W_r); I = top-k(s + b); w_i = scale s_i /
            (sum_{j in I} s_j + 1e-6); ffn(x) = sum_{i in I, i held} w_i
            SwiGLU_i(x): EVERY held expert computed for every token and
            weighted by the picks (0 where a token did not pick it)

It takes the benchmark's weights (benchmark/families/conv_gqa_moe.make_weights:
arrays in the served type, matrices [in, out]) and up-casts a layer's
matrices, and an expert at a time, so that 5.27 B parameters in bf16 and one
expert in float32 fit the chip together.  A tied head is the embedding table,
transposed.

``quant=`` puts something lower in the reference's place.  The controls:
``"int8"`` every matmul by a weight (the router's too) in W8A8; ``"taps1"``
the convolution's CURRENT tap alone (k[:, 2] v_t: what a trunk computes whose
state a slot reads zero every iteration).  And one WITNESS, ``"bf16"``: every
matmul by a weight with both sides rounded to bfloat16, and what a serving
cache or state would store (keys, values, the convolution's inputs) rounded
too: what the stated precision alone does to this model's logits, no program."""
import functools

import jax
import jax.numpy as jnp

from benchmark.harness import loader

_BASE = loader.load_module("references", "mla_moe")
_rms, _q8, weight_of, _cut = _BASE._rms, _BASE._q8, _BASE.weight_of, _BASE._cut

F32 = jnp.float32
MECHANISM = ("taps1",)       # ``quant`` values that change the mechanism, not the precision


def _precision(quant):
    return None if quant in MECHANISM else quant


def _mm(x, w, quant):
    if quant == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=F32)
    return _BASE._mm(x, w, quant)


def _stored(x, quant):
    """``x`` as a bfloat16 cache would hand it back (the witness alone)."""
    return x.astype(jnp.bfloat16).astype(F32) if quant == "bf16" else x


def held_range(cfg):
    lo, hi = cfg.get("experts_held", (0, cfg["num_experts"]))
    return int(lo), int(hi)


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def _rope(x, theta):
    """x: [S, H, D]; positions 0..S-1; halves rotated (the HF convention)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(x, wg, wu, wd, quant=None):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


# ---------------------------------------------------------------- operators
def short_conv(p, x, quant=None):
    """The gated short convolution over one sequence, x [S, E] already normed."""
    mq = _precision(quant)
    b, c, u = jnp.split(_mm(x, p["w_in"], mq), 3, axis=-1)
    v = _stored(b * u, quant)
    k = p["conv_k"].astype(F32)
    out = v * k[:, -1]
    if quant != "taps1":
        for back in range(1, k.shape[1]):                  # an explicit shift a tap
            shifted = jnp.concatenate([jnp.zeros((back, v.shape[1]), F32), v[:-back]])
            out = out + shifted * k[:, -1 - back]
    return _mm(c * out, p["w_out"], mq)


def attention(p, x, *, heads, kv_heads, dim, eps, theta, quant=None):
    """Grouped-query attention over one sequence under a full causal mask, a
    key/value head (and its group of query heads) at a time."""
    mq = _precision(quant)
    s = x.shape[0]
    q = _rope(_rms(_mm(x, p["wq"], mq).reshape(s, heads, dim), p["q_norm"], eps), theta)
    k = _rope(_rms(_mm(x, p["wk"], mq).reshape(s, kv_heads, dim), p["k_norm"], eps), theta)
    k, v = _stored(k, quant), _stored(_mm(x, p["wv"], mq).reshape(s, kv_heads, dim), quant)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(args):
        qg, kg, vg = args                                  # [S, G, D], [S, D], [S, D]
        sc = jnp.einsum("qgd,kd->gqk", qg, kg, precision="highest") * dim ** -0.5
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", pr, vg, precision="highest")

    qg = jnp.moveaxis(q.reshape(s, kv_heads, heads // kv_heads, dim), 1, 0)
    o = jax.lax.map(group, (qg, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return _mm(jnp.moveaxis(o, 0, 1).reshape(s, heads * dim), p["wo"], mq)


def route(x, w_router, bias, top_k, scale, quant=None):
    """-> (idx [S, k], w [S, k]) over every routed expert the router has."""
    g = jax.nn.sigmoid(_mm(x, w_router, _precision(quant)))
    idx = jax.lax.top_k(g + bias.astype(F32), top_k)[1]
    gv = jnp.take_along_axis(g, idx, axis=-1)
    return idx, scale * gv / (jnp.sum(gv, axis=-1, keepdims=True) + 1e-6)


# ------------------------------------------------------- jitted pieces, cached
@functools.lru_cache(maxsize=None)
def _jit_op(kind, eps, quant, **dims):
    def op(p, x):
        """x + op(n_operator(x)) and n_ffn of it."""
        xn = _rms(x, p["ln_op"], eps)
        h = x + (short_conv(p, xn, quant) if kind == "conv"
                 else attention(p, xn, eps=eps, quant=quant, **dims))
        return h, _rms(h, p["ln_ffn"], eps)
    return jax.jit(op)


@functools.lru_cache(maxsize=None)
def _jit_ffn(quant):
    return jax.jit(functools.partial(swiglu, quant=quant))


@functools.lru_cache(maxsize=None)
def _jit_route(top_k, scale, quant):
    return jax.jit(functools.partial(route, top_k=top_k, scale=scale, quant=quant))


@functools.lru_cache(maxsize=None)
def _jit_expert(quant):
    def add(acc, x, idx, w, expert, wg, wu, wd):
        return acc + weight_of(idx, w, expert)[:, None] * swiglu(x, wg, wu, wd, quant)
    return jax.jit(add, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jit_add():
    return jax.jit(lambda h, ffn: h + ffn)


@functools.lru_cache(maxsize=None)
def _jit_head(eps, quant, tied):
    def head(norm_w, head_w, x, rows):
        return _mm(_rms(x[rows], norm_w, eps), head_w.T if tied else head_w, quant)
    return jax.jit(head)


_OP = {"conv": ("ln_op", "ln_ffn", "w_in", "conv_k", "w_out"),
       "full_attention": ("ln_op", "ln_ffn", "wq", "wk", "wv", "wo", "q_norm", "k_norm")}


def layer_forward(p, x, cfg, quant=None):
    """One decoder layer over one sequence, x [S, E] float32: a convolution
    if the layer's leaves hold ``w_in``, else attention; dense if they hold
    ``wg``, else of the expert kind."""
    mq = _precision(quant)
    kind = "conv" if "w_in" in p else "full_attention"
    dims = {} if kind == "conv" else dict(
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        dim=head_dim(cfg), theta=float(cfg["rope_parameters"]["rope_theta"]))
    h, hn = _jit_op(kind, cfg["norm_eps"], quant, **dims)({k: p[k] for k in _OP[kind]}, x)
    if "wg" in p:
        ffn = _jit_ffn(mq)(hn, p["wg"], p["wu"], p["wd"])
    else:
        idx, w = _jit_route(cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]),
                            quant)(hn, p["router"], p["router_bias"])
        lo, hi = held_range(cfg)
        ffn = jnp.zeros_like(h)
        for e in range(hi - lo):                                  # an expert at a time
            ffn = _jit_expert(mq)(ffn, hn, idx, w, jnp.asarray(lo + e, jnp.int32),
                                  p["eg"][e], p["eu"][e], p["ed"][e])
    return _jit_add()(h, ffn)


def hidden_states(weights, cfg, ids, quant=None):
    """[S, E] float32: the last layer's output, before the final norm."""
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(F32)
    for p in weights["layers"]:
        x = layer_forward(p, x, cfg, quant)
    return x


def logits_at(weights, cfg, ids, rows, quant=None, n_prompt=0):
    """Logits [len(rows), V] float32 of the full forward over ``ids`` [S] at
    the positions ``rows``: row r predicts token r + 1.  ``n_prompt`` is part
    of the references' common signature; nothing here reads it."""
    x = hidden_states(weights, cfg, _cut(ids, rows), quant)
    tied = "head" not in weights
    return _jit_head(cfg["norm_eps"], _precision(quant), tied)(
        weights["norm"], weights["embed" if tied else "head"], x,
        jnp.asarray(rows, jnp.int32))
