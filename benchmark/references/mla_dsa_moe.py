"""The plain reference of the ``mla_dsa_moe`` family (DeepSeek-V3.2-Exp,
``model_type`` ``deepseek_v32``): multi-head latent attention in its
NON-ABSORBED form over a LEARNED selection, two RMSNorms a layer, YaRN rope,
leading dense SwiGLU layers, then sigmoid experts routed under a group limit
with a selection bias beside one shared expert; and the next-token module.
Straightforward jax.numpy in float32 under ``highest`` matmul precision: no
cache, no batching, no kernel, nothing imported from the program.  The pieces
that are the ``mla_moe`` reference's letter for letter (RMSNorm, the W8A8
matmul, an expert's weight, the cut of a padded sequence) are taken from
benchmark/references/mla_moe.py.

    layer:   h = x + MLA(n_in(x));  y = h + FFN(n_post(h))
    MLA:     c_q = n_q(x W_qa); [q_nope | q_rope] = c_q W_qb a head; [c | k_r]
             = x W_kva; c = n_kv(c); rope on q_rope and on the one k_r;
             [k_nope | v] = c W_kvb a head; s = (q_nope.k_nope + q_rope.k_r) *
             (nope + rope)**-0.5 * mscale**2; softmax over S_t; o = sum p v; W_o
    YaRN:    inv_i = theta**(-2i/R); (lo, hi) = floor / ceil of R ln(orig /
             (beta 2 pi)) / (2 ln theta) for beta_fast / beta_slow, inside
             [0, R/2 - 1]; ramp_i = clip((i - lo) / (hi - lo), 0, 1); inv'_i =
             inv_i / factor * ramp_i + inv_i (1 - ramp_i); mscale = 0.1
             mscale_all_dim ln(factor) + 1
    indexer: q^I_j = (c_q W_iq)_j, j < index_n_heads; k^I = LayerNorm(x W_ik)
             (gain, bias, eps 1e-6); rope on the first R dimensions of both;
             w = (x W_iw) index_n_heads**-0.5 index_head_dim**-0.5;
             I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])
    S_t:     the min(index_topk, t + 1) positions s <= t of highest I[t, s]:
             everything above the ``jax.lax.top_k``'s last value and, of the
             positions equal to it, the lowest (top_k's own order of ties)
    experts: g = sigmoid(x W_r); g' = g + b; n_group groups scored by the sum
             of their two highest g'; the topk_group best stay; I = top-k of
             g' inside them; w_i = scale g_i / sum_{j in I} g_j;
             FFN(x) = SwiGLU_shared(x) + sum_{i in I, i held} w_i SwiGLU_i(x)

It is given the same SHARE of the deployment as the program (``experts_held``
of ``router_outputs``, the vocabulary's slice), takes the benchmark's weights
(benchmark/families/mla_dsa_moe.make_weights) and up-casts a matrix at a time.
So that 17 k tokens fit beside them, a layer's selection is made in blocks of
query positions and kept as a mask [S, S], and the attention runs a group of
heads at a time, in blocks of query positions.

``quant=`` puts something lower in the reference's place (the controls):
``"int8"`` every matmul by a weight in W8A8, as the ``mla_moe`` reference;
``"recent"`` the ``index_topk`` most recent positions in the indexer's place;
``"dense"`` every position (no selection): the mechanism left out.  And one
WITNESS, ``"bf16"``: every matmul by a weight with both sides rounded to
bfloat16 and what a serving cache would store (the latent, the rope key, the
index key) and the indexer's queries rounded too, everything else as it is:
what the stated precision alone does to this model's logits, no program."""
import functools
import math

import jax
import jax.numpy as jnp

from benchmark.harness import loader

_BASE = loader.load_module("references", "mla_moe")
_rms, held_range, weight_of = _BASE._rms, _BASE.held_range, _BASE.weight_of

F32 = jnp.float32
Q_BLOCK = 64           # query positions selected for, and attended, at a time
HEAD_GROUPS = 4        # the attention runs this many groups of heads in turn
MECHANISM = ("recent", "dense")       # ``quant`` values that change the selection


def _precision(quant):
    """The matmuls' ``quant``: the mechanism's controls keep float32."""
    return None if quant in MECHANISM else quant


def _mm(x, w, quant):
    if quant == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=F32)
    return _BASE._mm(x, w, quant)


def _stored(x, quant):
    """``x`` as a bfloat16 cache would hand it back (the witness alone)."""
    return x.astype(jnp.bfloat16).astype(F32) if quant == "bf16" else x


def swiglu(x, wg, wu, wd, quant=None):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def yarn_inv_freq(r, theta, scaling):
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    if scaling is None:
        return inv

    def turns(beta):
        return (r * math.log(scaling["original_max_position_embeddings"] / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns(scaling["beta_fast"])), 0)
    hi = min(math.ceil(turns(scaling["beta_slow"])), r // 2 - 1)
    ramp = jnp.clip((jnp.arange(r // 2, dtype=F32) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv / scaling["factor"] * ramp + inv * (1.0 - ramp)


def mscale(scaling, which):
    if scaling is None or scaling["factor"] <= 1 or not scaling.get(which, 0):
        return 1.0
    return 0.1 * scaling[which] * math.log(scaling["factor"]) + 1.0


def _rope(x, r, theta, scaling):
    """Rope on the first ``r`` of the last axis; x: [S, H, D]; positions
    0..S-1; halves rotated (the HF convention)."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(r, theta, scaling)[None, :]
    m = mscale(scaling, "mscale") / mscale(scaling, "mscale_all_dim")
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = x[..., : r // 2], x[..., r // 2: r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)


def _layer_norm(x, gain, bias, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain.astype(F32) + bias.astype(F32)


def _dims(cfg):
    return dict(heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], vdim=cfg["v_head_dim"],
                rank=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"],
                theta=float(cfg["rope_theta"]), index_heads=cfg["index_n_heads"],
                index_dim=cfg["index_head_dim"], topk=cfg["index_topk"],
                scaling=tuple(sorted((cfg.get("rope_scaling") or {}).items())) or None)


def _blocks(x, n):
    """[S, ...] -> [S/n rounded up, n, ...], zero padded."""
    pad = (-x.shape[0]) % n
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape((-1, n) + x.shape[1:])


# ------------------------------------------------------------------ selection
def selection(p, x, c_q, *, rope, theta, scaling, index_heads, index_dim, topk, quant):
    """[S, S] bool: row t the positions query t may attend."""
    s = x.shape[0]
    kpos = jnp.arange(s)
    if quant in MECHANISM:
        qpos = kpos[:, None]
        recent = kpos[None, :] > qpos - topk if quant == "recent" else True
        return (kpos[None, :] <= qpos) & recent
    mq = _precision(quant)
    qi = _rope(_mm(c_q, p["wiq"], mq).reshape(s, index_heads, index_dim), rope, theta, scaling)
    ki = _rope(_layer_norm(_mm(x, p["wik"], mq), p["ik_norm_w"], p["ik_norm_b"])[:, None],
               rope, theta, scaling)[:, 0]
    w = _mm(x, p["wiw"], mq) * (index_heads ** -0.5 * index_dim ** -0.5)
    qi, ki = _stored(qi, quant), _stored(ki, quant)
    k = min(topk, s)

    def block(args):
        qb, wb, at = args
        dots = jnp.einsum("qjd,kd->qjk", qb, ki, precision="highest")
        score = jnp.sum(jax.nn.relu(dots) * wb[..., None], axis=1)            # [Q, S]
        causal = kpos[None, :] <= (at * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        score = jnp.where(causal, score, -jnp.inf)
        last = jax.lax.top_k(score, k)[0][:, -1:]
        above, ties = score > last, score == last
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        return (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))) & causal

    qb, wb = _blocks(qi, Q_BLOCK), _blocks(w, Q_BLOCK)
    return jax.lax.map(block, (qb, wb, jnp.arange(qb.shape[0]))).reshape(-1, s)[:s]


# ------------------------------------------------------------------ attention
def mla(p, x, *, heads, nope, rope, vdim, rank, eps, theta, scaling, index_heads,
        index_dim, topk, quant=None):
    """Latent attention over one sequence under the selection. x: [S, E]
    float32, already normed."""
    s = x.shape[0]
    mq = _precision(quant)
    c_q = _rms(_mm(x, p["wq_a"], mq), p["q_norm"], eps)
    kv_a = _mm(x, p["wkv_a"], mq)
    c = _stored(_rms(kv_a[:, :rank], p["kv_norm"], eps), quant)
    k_r = _stored(_rope(kv_a[:, None, rank:], rope, theta, scaling)[:, 0], quant)   # [S, R]
    visible = _blocks(selection(p, x, c_q, rope=rope, theta=theta, scaling=scaling,
                                index_heads=index_heads, index_dim=index_dim, topk=topk,
                                quant=quant), Q_BLOCK)
    scale = (nope + rope) ** -0.5 * mscale(scaling, "mscale_all_dim") ** 2
    g = heads // HEAD_GROUPS if heads % HEAD_GROUPS == 0 else heads
    wq_b = p["wq_b"].reshape(-1, heads, nope + rope)
    wkv_b = p["wkv_b"].reshape(rank, heads, nope + vdim)
    wo = p["wo"].reshape(heads, vdim, -1)
    out = 0.0
    for h0 in range(0, heads, g):
        q = _mm(c_q, wq_b[:, h0:h0 + g].reshape(-1, g * (nope + rope)), mq)
        q = q.reshape(s, g, nope + rope)
        q_n, q_r = q[..., :nope], _rope(q[..., nope:], rope, theta, scaling)
        kv = _mm(c, wkv_b[:, h0:h0 + g].reshape(rank, -1), mq).reshape(s, g, nope + vdim)
        k_n, v = kv[..., :nope], kv[..., nope:]

        def block(args):
            qn, qr, vis = args
            sc = (jnp.einsum("qhd,khd->hqk", qn, k_n, precision="highest")
                  + jnp.einsum("qhr,kr->hqk", qr, k_r, precision="highest")) * scale
            sc = jnp.where(vis[None], sc, -jnp.inf)
            sc = jnp.where(jnp.any(vis, axis=-1)[None, :, None], sc, 0.0)   # padded rows
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                              precision="highest")

        o = jax.lax.map(block, (_blocks(q_n, Q_BLOCK), _blocks(q_r, Q_BLOCK), visible))
        o = o.reshape(-1, g * vdim)[:s]
        out = out + _mm(o, wo[h0:h0 + g].reshape(g * vdim, -1), mq)
    return out


def route(x, w_router, bias, top_k, scale, n_group, topk_group, quant=None):
    """-> (idx [S, k], w [S, k]) over every routed expert the router has."""
    g = jax.nn.sigmoid(_mm(x, w_router, _precision(quant)))
    choice = (g + bias.astype(F32)).reshape(g.shape[0], n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(choice, 2)[0], axis=-1)
    best = jax.lax.top_k(group_score, topk_group)[1]
    kept = jnp.zeros(group_score.shape, bool).at[jnp.arange(g.shape[0])[:, None], best].set(True)
    choice = jnp.where(kept[..., None], choice, -jnp.inf).reshape(g.shape)
    idx = jax.lax.top_k(choice, top_k)[1]
    gv = jnp.take_along_axis(g, idx, axis=-1)
    return idx, scale * gv / jnp.sum(gv, axis=-1, keepdims=True)


# ------------------------------------------------------- jitted pieces, cached
@functools.lru_cache(maxsize=None)
def _jit_attn(quant, **dims):
    dims = dict(dims, scaling=dict(dims["scaling"]) if dims["scaling"] else None)
    eps = dims["eps"]

    def attn(p, x):
        """x + MLA(n_in(x)) and n_post of it."""
        h = x + mla(p, _rms(x, p["ln_in"], eps), quant=quant, **dims)
        return h, _rms(h, p["ln_post"], eps)
    return jax.jit(attn)


@functools.lru_cache(maxsize=None)
def _jit_route(top_k, scale, n_group, topk_group, quant):
    return jax.jit(functools.partial(route, top_k=top_k, scale=scale, n_group=n_group,
                                     topk_group=topk_group, quant=quant))


@functools.lru_cache(maxsize=None)
def _jit_add():
    return jax.jit(lambda h, ffn: h + ffn)


@functools.lru_cache(maxsize=None)
def _jit_ffn(quant):
    return jax.jit(functools.partial(swiglu, quant=quant))


@functools.lru_cache(maxsize=None)
def _jit_expert(quant):
    def add(acc, x, idx, w, expert, wg, wu, wd):
        return acc + weight_of(idx, w, expert)[:, None] * swiglu(x, wg, wu, wd, quant)
    return jax.jit(add, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jit_head(eps, quant):
    def head(norm_w, head_w, x, rows):
        return _mm(_rms(x[rows], norm_w, eps), head_w, quant)
    return jax.jit(head)


_ATTN = ("ln_in", "ln_post", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
         "wiq", "wik", "ik_norm_w", "ik_norm_b", "wiw")


def layer_forward(p, x, cfg, quant=None):
    """One decoder layer over one sequence, x [S, E] float32: dense if the
    layer's leaves hold ``wg``, else of the expert kind."""
    mq = _precision(quant)
    h, hn = _jit_attn(quant, **_dims(cfg))({k: p[k] for k in _ATTN}, x)
    if "wg" in p:
        ffn = _jit_ffn(mq)(hn, p["wg"], p["wu"], p["wd"])
    else:
        ffn = _jit_ffn(mq)(hn, p["sg"], p["su"], p["sd"])   # the shared expert
        idx, w = _jit_route(cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]),
                            cfg["n_group"], cfg["topk_group"], quant)(
                                hn, p["router"], p["router_bias"])
        lo, hi = held_range(cfg)
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
    x = hidden_states(weights, cfg, _BASE._cut(ids, rows), quant)
    return _jit_head(cfg["rms_norm_eps"], _precision(quant))(
        weights["norm"], weights["head"], x, jnp.asarray(rows, jnp.int32))


def mtp_logits_at(weights, cfg, ids, rows, quant=None):
    """The next-token module (``weights["mtp"]``): h' = W_p [n_a(h_t) ;
    n_b(Emb(tok_{t+1}))] over t = 0 .. S-2, one more layer of the expert kind,
    a norm, the model's output head.  Row r (< S - 1) predicts token r + 2."""
    m, eps, mq = weights["mtp"], cfg["rms_norm_eps"], _precision(quant)
    ids = jnp.asarray(ids, jnp.int32)
    h = hidden_states(weights, cfg, ids, quant)[:-1]
    emb = weights["embed"][ids[1:]].astype(F32)
    cat = jnp.concatenate([_rms(h, m["hnorm"], eps), _rms(emb, m["enorm"], eps)], axis=-1)
    x = layer_forward(m["layer"], _mm(cat, m["proj"], mq), cfg, quant)
    return _jit_head(eps, mq)(m["norm"], weights["head"], x, jnp.asarray(rows, jnp.int32))
