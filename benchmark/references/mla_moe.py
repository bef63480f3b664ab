"""The plain reference of the ``mla_moe`` family (openPangu-Ultra-MoE,
``model_type`` ``pangu_ultra_moe``): multi-head latent attention in its
NON-ABSORBED form (keys and values expanded a head from the latent, as the
equations are published), sandwich norms, leading dense SwiGLU layers, then
layers of sigmoid-routed experts beside one shared expert; and the next-token
(MTP) module.  Straightforward jax.numpy in float32 under ``highest`` matmul
precision: no cache, no batching, no kernel, nothing imported from the program.

    layer:  h = x + n_post_attn(MLA(n_in(x)));  y = h + n_post_mlp(FFN(n_pre_mlp(h)))
    MLA:    c_q = n_q(x W_qa); [q_nope | q_rope] = c_q W_qb a head;
            [c | k_r] = x W_kva; c = n_kv(c); rope on q_rope and on k_r (one
            k_r for all heads); [k_nope | v] = c W_kvb a head;
            s = (q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope); causal
            softmax; o = sum p v; concat_heads(o) W_o
    expert: g = sigmoid(x W_r) over all ``router_outputs``; I = top-k(g);
            w_i = scale g_i / (sum_{j in I} g_j + 1e-20);
            FFN(x) = SwiGLU_shared(x) + sum_{i in I, i held} w_i SwiGLU_i(x)

It is given the same SHARE of the deployment as the program: ``cfg`` (the
configuration file's dict) says which of the routed experts are held
(``experts_held``, [lo, hi) of ``router_outputs``; the weights hold just
those) and the vocabulary is the slice the weights hold.  What an absent
expert would have added is left out here as there.

It takes the benchmark's weights (benchmark/families/mla_moe.make_weights:
bf16 arrays, matrices [in, out]) and up-casts one matrix, or one expert, at a
time, and attends in blocks of query positions, so that it fits beside them.

``quant="int8"`` is the control: every matmul by a weight (the router's too)
in W8A8, as benchmark/references/llama_dense.py does it."""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 128          # query positions attended at a time


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    """x: [S, H, R]; positions 0..S-1; halves rotated (the HF convention)."""
    s, _, r = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _q8(x, axis):
    """Round to 8-bit integers, symmetric, one scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, quant):
    w = w.astype(F32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown lower precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def _dims(cfg):
    return dict(heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], vdim=cfg["v_head_dim"],
                rank=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"],
                theta=float(cfg["rope_theta"]))


def held_range(cfg):
    """[lo, hi) of the published routed experts that the weights hold."""
    lo, hi = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    return int(lo), int(hi)


# ------------------------------------------------------------------ attention
def mla(p, x, *, heads, nope, rope, vdim, rank, eps, theta, quant=None):
    """Latent attention over one sequence. x: [S, E] float32, already normed."""
    s = x.shape[0]
    q = _mm(_rms(_mm(x, p["wq_a"], quant), p["q_norm"], eps), p["wq_b"], quant)
    q = q.reshape(s, heads, nope + rope)
    kv_a = _mm(x, p["wkv_a"], quant)
    c = _rms(kv_a[:, :rank], p["kv_norm"], eps)
    k_r = _rope(kv_a[:, None, rank:], theta)                        # [S, 1, R]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    kv = _mm(c, p["wkv_b"], quant).reshape(s, heads, nope + vdim)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (s, heads, rope))], axis=-1)
    v = kv[..., nope:]

    pad = (-s) % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, heads, nope + rope)
    kpos = jnp.arange(s)

    def block(args):
        qi, at = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision="highest") * (nope + rope) ** -0.5
        qpos = at * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision="highest")

    o = jax.lax.map(block, (qb, jnp.arange(qb.shape[0])))
    o = o.reshape(-1, heads * vdim)[:s]
    return _mm(o, p["wo"], quant)


def swiglu(x, wg, wu, wd, quant=None):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def route(x, w_router, top_k, scale, quant=None):
    """-> (idx [S, k], w [S, k]) over every routed expert the router has."""
    g = jax.nn.sigmoid(_mm(x, w_router, quant))
    gv, idx = jax.lax.top_k(g, top_k)
    return idx, scale * gv / (jnp.sum(gv, axis=-1, keepdims=True) + 1e-20)


def weight_of(idx, w, expert):
    """[S]: the weight each token gives ``expert`` (0 where it did not pick it)."""
    return jnp.sum(jnp.where(idx == expert, w, 0.0), axis=-1)


# ------------------------------------------------------- jitted pieces, cached
@functools.lru_cache(maxsize=None)
def _jit_attn(eps, quant, **dims):
    def attn(p, x):
        """x + n_post_attn(MLA(n_in(x))) and n_pre_mlp of it."""
        a = mla(p, _rms(x, p["ln_in"], eps), eps=eps, quant=quant, **dims)
        h = x + _rms(a, p["ln_post_attn"], eps)
        return h, _rms(h, p["ln_pre_mlp"], eps)
    return jax.jit(attn)


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
def _jit_close(eps):
    return jax.jit(lambda h, ffn, gain: h + _rms(ffn, gain, eps))


@functools.lru_cache(maxsize=None)
def _jit_head(eps, quant):
    def head(norm_w, head_w, x, rows):
        return _mm(_rms(x[rows], norm_w, eps), head_w, quant)
    return jax.jit(head)


def _attn_leaves(p):
    return {k: p[k] for k in ("ln_in", "ln_post_attn", "ln_pre_mlp", "wq_a", "q_norm",
                              "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")}


def layer_forward(p, x, cfg, quant=None):
    """One decoder layer over one sequence, x [S, E] float32: dense if the
    layer's leaves hold ``wg``, else of the expert kind."""
    dims = _dims(cfg)
    eps = dims.pop("eps")
    h, hn = _jit_attn(eps, quant, **dims)(_attn_leaves(p), x)
    if "wg" in p:
        ffn = _jit_ffn(quant)(hn, p["wg"], p["wu"], p["wd"])
    else:
        ffn = _jit_ffn(quant)(hn, p["sg"], p["su"], p["sd"])      # the shared expert
        idx, w = _jit_route(cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]),
                            quant)(hn, p["router"])
        lo, hi = held_range(cfg)
        for e in range(hi - lo):                                  # an expert at a time
            ffn = _jit_expert(quant)(ffn, hn, idx, w, jnp.asarray(lo + e, jnp.int32),
                                     p["eg"][e], p["eu"][e], p["ed"][e])
    return _jit_close(eps)(h, ffn, p["ln_post_mlp"])


def hidden_states(weights, cfg, ids, quant=None):
    """[S, E] float32: the last layer's output, before the final norm."""
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(F32)
    for p in weights["layers"]:
        x = layer_forward(p, x, cfg, quant)
    return x


LENGTHS = 4            # a sequence is cut to one of this many lengths


def _cut(ids, rows):
    """``ids`` without the tail that no row of ``rows`` can see (causal), its
    length rounded up to a quarter of what was given: a request of 2,000
    tokens padded to 8,704 costs a quarter of the longest one's forward, and
    the jitted pieces compile for four lengths, not for every length."""
    step = -(-len(ids) // LENGTHS)
    need = int(max(rows)) + 1
    return ids[:min(len(ids), -(-need // step) * step)]


def logits_at(weights, cfg, ids, rows, quant=None, n_prompt=0):
    """Logits [len(rows), V] float32 of the full forward over ``ids`` [S] at
    the positions ``rows``: row r predicts token r + 1.  ``n_prompt`` is part
    of the references' common signature; nothing here reads it."""
    x = hidden_states(weights, cfg, _cut(ids, rows), quant)
    return _jit_head(cfg["rms_norm_eps"], quant)(
        weights["norm"], weights["head"], x, jnp.asarray(rows, jnp.int32))


def mtp_logits_at(weights, cfg, ids, rows, quant=None):
    """The next-token module (``weights["mtp"]``): h' = W_p [n_a(h_t) ;
    n_b(Emb(tok_{t+1}))] over t = 0 .. S-2, one more layer of the expert kind,
    a norm, the model's output head.  Row r (< S - 1) predicts token r + 2."""
    m, eps = weights["mtp"], cfg["rms_norm_eps"]
    ids = jnp.asarray(ids, jnp.int32)
    h = hidden_states(weights, cfg, ids, quant)[:-1]
    emb = weights["embed"][ids[1:]].astype(F32)
    cat = jnp.concatenate([_rms(h, m["hnorm"], eps), _rms(emb, m["enorm"], eps)], axis=-1)
    x = layer_forward(m["layer"], _mm(cat, m["proj"], quant), cfg, quant)
    return _jit_head(eps, quant)(m["norm"], weights["head"], x, jnp.asarray(rows, jnp.int32))
