"""openPangu-Ultra-MoE (``model_type`` ``pangu_ultra_moe``): a decoder of
multi-head LATENT attention (MLA), SANDWICH norms (four RMSNorms a layer),
leading dense SwiGLU layers and then sparse layers of sigmoid-routed experts
beside one shared expert, with a next-token (MTP) module.  Published config:
https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json
whose key names ``PanguUltraMoEConfig`` keeps.

Layer (x: [T, E]; ``n`` an RMSNorm with a learned gain):
    h = x + n_post_attn(MLA(n_in(x)));  y = h + n_post_mlp(FFN(n_pre_mlp(h)))
MLA: c_q = n_q(x W_qa); [q_nope | q_rope] = c_q W_qb a head; [c | k_r] =
    x W_kva; c = n_kv(c); rope (rotate-half) on q_rope and on the ONE k_r all
    heads share; [k_nope | v] = c W_kvb a head; softmax((q_nope.k_nope +
    q_rope.k_r) / sqrt(nope + rope)) v, then W_o.
Expert FFN: g = sigmoid(float32(x) float32(W_r)) over ALL n_routed_experts;
    I = top-k(g); w_i = scale * g_i / (sum_{j in I} g_j + 1e-20);
    FFN(x) = SwiGLU_shared(x) + sum_{i in I, i held} w_i SwiGLU_i(x).

``experts_held`` (lo, hi) is part of the model: a chip of an expert-parallel
deployment holds a range of the routed experts, routes over all of them, and
computes the part of the result its own experts give.  A pick of an expert
that lives elsewhere adds nothing here: no code stands in for the absent
chips or their exchange.

Two forms of the same mathematics: the layers' ``forward`` (whole sequences,
keys and values expanded a head: the published equations as they stand) and
``serving_trunk`` (packed tokens against the engine's paged latent cache,
ops/latent_attention.py: queries absorbed into the latent space)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import Constant, Normal
from ..ops.dispatch import apply
from ..ops.held_experts import _swiglu, held_experts
from ..ops.latent_attention import latent_attention, latent_counts, rope_half
from ..profiler import SetupSpan

__all__ = ["PanguUltraMoEConfig", "PanguUltraMoEModel", "PanguUltraMoEForCausalLM",
           "PanguSparseMoE", "PanguMLAttention", "pangu_ultra_moe_tiny"]

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class LatentMoEGeometry:
    """What a config of latent attention and held experts derives from its
    published keys (shared with models/deepseek_v32.py)."""

    def _check_experts_held(self):
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held={self.experts_held} is no range of "
                             f"{self.n_routed_experts} routed experts")
        self.experts_held = (lo, hi)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values of one cache entry: the latent and the shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_cache_width(self) -> int:
        """Width an entry is STORED at: whole 128-lane tiles once it is wider
        than one.  At 576 the TPU compiler gives the pool a transposed device
        layout and copies all of it into and out of every program (a layer's
        pool as temporaries; 0.5 MB at 640: compile, PR 26); the padding is
        zeros, which a score's dot ignores."""
        w = self.latent_width
        return -(-w // 128) * 128 if w > 128 else w

    def is_sparse(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


@dataclass
class PanguUltraMoEConfig(LatentMoEGeometry):
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    sandwich_norm: bool = True
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    dtype: str = "float32"
    # the routed experts this chip holds, [lo, hi) of n_routed_experts; None: all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        self._check_experts_held()
        if (not self.sandwich_norm or not self.norm_topk_prob or self.attention_bias
                or self.hidden_act != "silu" or self.n_shared_experts != 1
                or self.tie_word_embeddings):
            raise ValueError("pangu_ultra_moe as published: sandwich norms, top-k "
                             "weights normalised, one shared expert, SwiGLU, no "
                             "biases, untied head")


def pangu_ultra_moe_tiny(**kw) -> PanguUltraMoEConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
                num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                n_routed_experts=16, num_experts_per_tok=4, num_nextn_predict_layers=0,
                max_position_embeddings=256, rope_theta=10000.0)
    base.update(kw)
    return PanguUltraMoEConfig(**base)


# ------------------------------------------------------------ the mathematics
# Pure functions of arrays, shared by the layers' ``forward`` and the trunk.
def _rms(x, w, eps):
    xf = x.astype(F32)
    nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (nrm * w.astype(F32)).astype(x.dtype)


def rope_table(cfg, length):
    """[2, length, R/2] float32 (cos, sin): rotate-half, no scaling."""
    r = cfg.qk_rope_head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    fr = np.outer(np.arange(length, dtype=np.float64), inv)
    return jnp.asarray(np.stack([np.cos(fr), np.sin(fr)]), F32)


def _q_latent(cfg, p, x):
    """x [T, E] -> c_q [T, q_lora_rank]: the queries' normed latent."""
    return _rms(x @ p["wq_a"], p["q_norm"], cfg.rms_norm_eps)


def _latent_proj(cfg, p, x, cos, sin, c_q=None):
    """x [T, E] -> q_nope [T, H, N], rope(q_rope) [T, H, R], c [T, C],
    rope(k_r) [T, R]; cos/sin [T, R/2] at each token's position.  ``c_q``:
    ``_q_latent`` of the same ``x``, where the caller needs it too."""
    H, N, R = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps = cfg.rms_norm_eps
    q = ((_q_latent(cfg, p, x) if c_q is None else c_q) @ p["wq_b"]).reshape(-1, H, N + R)
    kv = x @ p["wkv_a"]
    c = _rms(kv[:, :cfg.kv_lora_rank], p["kv_norm"], eps)
    k_r = rope_half(kv[:, cfg.kv_lora_rank:], cos, sin)
    return q[..., :N], rope_half(q[..., N:], cos, sin), c, k_r


def _mla_full(cfg, p, x):
    """One sequence [S, E], causal, keys and values expanded a head."""
    H, N, V = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    S = x.shape[0]
    rope = rope_table(cfg, S)
    q_n, q_r, c, k_r = _latent_proj(cfg, p, x, rope[0], rope[1])
    kv = (c @ p["wkv_b"]).reshape(S, H, N + V)
    s = (jnp.einsum("qhn,khn->hqk", q_n, kv[..., :N], preferred_element_type=F32)
         + jnp.einsum("qhr,kr->hqk", q_r, k_r, preferred_element_type=F32))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s * cfg.qk_head_dim ** -0.5, -1e30)
    o = jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1).astype(x.dtype), kv[..., N:])
    return o.reshape(S, H * V) @ p["wo"]


@jax.named_scope("router")
def route(x, w_router, top_k, scale):
    """Sigmoid scores in float32 over every routed expert, plain top-k, the
    chosen weights normalised over all ``top_k`` and scaled.
    -> (idx [T, k] int32, w [T, k] float32)."""
    g = jax.nn.sigmoid(jnp.dot(x.astype(F32), w_router.astype(F32), precision=HIGHEST))
    gv, idx = jax.lax.top_k(g, top_k)
    return idx.astype(jnp.int32), scale * gv / (jnp.sum(gv, -1, keepdims=True) + 1e-20)


def route_chosen(logits, top_k):
    """The softmax-over-the-chosen form: the ``top_k`` largest of a token's router
    ``logits`` [T, n] (float32, computed wherever the model places its router), the
    weights a softmax over THOSE alone.  -> (idx [T, k] int32, w [T, k] float32)."""
    gv, idx = jax.lax.top_k(logits.astype(F32), top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(gv, axis=-1)


def _moe_ffn(cfg, p, x, valid=None, router=route, counts=None):
    """Shared expert (where the layer's weights hold one: ``sg``) + the held
    experts' part. -> (y in x's dtype, picks).
    ``router(x, w_router, top_k, scale)`` -> (idx, w): the model's routing;
    ``counts``: a trunk's dict, ``held_experts``'s; where it holds them the layer
    adds ``moe_tokens`` (the ``valid`` tokens through it) and ``moe_local_picks``."""
    idx, w = router(x, p["router"], cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    routed, picks = held_experts(x, idx, w, p["eg"], p["eu"], p["ed"],
                                 cfg.experts_held[0], valid, counts=counts,
                                 routed=p["router"].shape[-1])
    if "sg" not in p:
        y = routed.astype(x.dtype)
    else:
        with jax.named_scope("shared_expert"):
            shared = _swiglu(x, p["sg"], p["su"], p["sd"])
        y = (shared.astype(F32) + routed).astype(x.dtype)
    if counts is not None and "moe_tokens" in counts:
        counts["moe_tokens"] += jnp.sum(valid).astype(jnp.int32)
    if counts is not None and "moe_local_picks" in counts:
        counts["moe_local_picks"] += picks
    return y, picks


# ------------------------------------------------------------------ the layers
class _Dense(nn.Layer):
    """y = x W, W [in, out], no bias."""

    def __init__(self, n_in, n_out, dtype):
        super().__init__()
        self.weight = self.create_parameter(
            [n_in, n_out], dtype=dtype, default_initializer=Normal(0.0, n_in ** -0.5))


class _Gain(nn.Layer):
    """An RMSNorm's learned gain."""

    def __init__(self, n, dtype):
        super().__init__()
        self.weight = self.create_parameter([n], dtype=dtype,
                                            default_initializer=Constant(1.0))


def _apply(fn, tree, *xs, n_outs=1):
    """``fn(arrays of tree, *arrays of xs)`` through the eager dispatch (so
    that it is taped), ``tree`` a dict of parameter Tensors."""
    keys = sorted(tree)

    def run(*vals):
        return fn(dict(zip(keys, vals[:len(keys)])), *vals[len(keys):])

    return apply(run, *[tree[k] for k in keys], *xs, op_name=fn.__name__, n_outs=n_outs)


class PanguMLAttention(nn.Layer):
    def __init__(self, cfg: PanguUltraMoEConfig):
        super().__init__()
        self.cfg = cfg
        e, h, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
        self.q_a_proj = _Dense(e, cfg.q_lora_rank, dt)
        self.q_a_layernorm = _Gain(cfg.q_lora_rank, dt)
        self.q_b_proj = _Dense(cfg.q_lora_rank, h * cfg.qk_head_dim, dt)
        self.kv_a_proj_with_mqa = _Dense(e, cfg.latent_width, dt)
        self.kv_a_layernorm = _Gain(cfg.kv_lora_rank, dt)
        self.kv_b_proj = _Dense(cfg.kv_lora_rank,
                                h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dt)
        self.o_proj = _Dense(h * cfg.v_head_dim, e, dt)

    def leaves(self):
        return {"wq_a": self.q_a_proj.weight, "q_norm": self.q_a_layernorm.weight,
                "wq_b": self.q_b_proj.weight, "wkv_a": self.kv_a_proj_with_mqa.weight,
                "kv_norm": self.kv_a_layernorm.weight, "wkv_b": self.kv_b_proj.weight,
                "wo": self.o_proj.weight}

    def forward(self, hidden):
        """hidden [B, S, E] -> [B, S, E], causal over each sequence."""
        cfg = self.cfg

        def mla(p, x):
            return jax.vmap(lambda seq: _mla_full(cfg, p, seq))(x)

        return _apply(mla, self.leaves(), hidden)


class PanguMLP(nn.Layer):
    def __init__(self, cfg: PanguUltraMoEConfig, width: int):
        super().__init__()
        self.gate_proj = _Dense(cfg.hidden_size, width, cfg.dtype)
        self.up_proj = _Dense(cfg.hidden_size, width, cfg.dtype)
        self.down_proj = _Dense(width, cfg.hidden_size, cfg.dtype)

    def leaves(self):
        return {"wg": self.gate_proj.weight, "wu": self.up_proj.weight,
                "wd": self.down_proj.weight}

    def forward(self, x):
        def mlp(p, x):
            return _swiglu(x, p["wg"], p["wu"], p["wd"])

        return _apply(mlp, self.leaves(), x)


class PanguSparseMoE(nn.Layer):
    """The expert layer: a router over all ``n_routed_experts``, the
    ``experts_held`` range of them as stacked SwiGLU weights, and the shared
    expert.  Its result is the shared expert's plus the held experts' part."""

    def __init__(self, cfg: PanguUltraMoEConfig):
        super().__init__()
        self.cfg = cfg
        e, f, dt = cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype
        lo, hi = cfg.experts_held
        self.experts_held = (lo, hi)
        self.gate = _Dense(e, cfg.n_routed_experts, dt)
        self.experts_gate = self.create_parameter(
            [hi - lo, e, f], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
        self.experts_up = self.create_parameter(
            [hi - lo, e, f], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
        self.experts_down = self.create_parameter(
            [hi - lo, f, e], dtype=dt, default_initializer=Normal(0.0, f ** -0.5))
        self.shared_experts = PanguMLP(cfg, f * cfg.n_shared_experts)

    def leaves(self):
        s = self.shared_experts.leaves()
        return {"router": self.gate.weight, "eg": self.experts_gate,
                "eu": self.experts_up, "ed": self.experts_down,
                "sg": s["wg"], "su": s["wu"], "sd": s["wd"]}

    def forward(self, x):
        cfg = self.cfg

        def moe_ffn(p, x):
            y, _ = _moe_ffn(cfg, p, x.reshape(-1, x.shape[-1]))
            return y.reshape(x.shape)

        return _apply(moe_ffn, self.leaves(), x)


class PanguDecoderLayer(nn.Layer):
    def __init__(self, cfg: PanguUltraMoEConfig, sparse: bool):
        super().__init__()
        self.cfg = cfg
        e, dt = cfg.hidden_size, cfg.dtype
        self.input_layernorm = _Gain(e, dt)
        self.self_attn = PanguMLAttention(cfg)
        self.post_attention_layernorm = _Gain(e, dt)
        self.pre_mlp_layernorm = _Gain(e, dt)
        self.mlp = PanguSparseMoE(cfg) if sparse else PanguMLP(cfg, cfg.intermediate_size)
        self.post_mlp_layernorm = _Gain(e, dt)

    def leaves(self):
        out = {"ln_in": self.input_layernorm.weight,
               "ln_post_attn": self.post_attention_layernorm.weight,
               "ln_pre_mlp": self.pre_mlp_layernorm.weight,
               "ln_post_mlp": self.post_mlp_layernorm.weight}
        out.update(self.self_attn.leaves())
        out.update(self.mlp.leaves())
        return out

    def _norm(self, x, gain):
        eps = self.cfg.rms_norm_eps

        def rms_norm(p, x):
            return _rms(x, p["w"], eps)

        return _apply(rms_norm, {"w": gain.weight}, x)

    def forward(self, x):
        h = x + self._norm(self.self_attn(self._norm(x, self.input_layernorm)),
                           self.post_attention_layernorm)
        return h + self._norm(self.mlp(self._norm(h, self.pre_mlp_layernorm)),
                              self.post_mlp_layernorm)


class PanguMTPModule(nn.Layer):
    """The next-token module: h' = W_p [n_a(h_t) ; n_b(Emb(tok_{t+1}))], one
    more layer of the expert kind, a norm, the model's own output head."""
    layer_class = PanguDecoderLayer

    def __init__(self, cfg):
        super().__init__()
        e, dt = cfg.hidden_size, cfg.dtype
        self.hnorm = _Gain(e, dt)
        self.enorm = _Gain(e, dt)
        self.eh_proj = _Dense(2 * e, e, dt)
        self.block = self.layer_class(cfg, sparse=True)
        self.norm = _Gain(e, dt)


class PanguUltraMoEModel(nn.Layer):
    layer_class = PanguDecoderLayer

    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Layer()
        self.embed_tokens.weight = self.embed_tokens.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(0.0, 1.0))
        self.layers = nn.LayerList([self.layer_class(cfg, cfg.is_sparse(i))
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Gain(cfg.hidden_size, cfg.dtype)

    def forward(self, input_ids):
        """[B, S] ids -> the last layer's output [B, S, E], before ``norm``."""
        h = apply(lambda w, ids: w[ids], self.embed_tokens.weight, input_ids,
                  op_name="embedding")
        for layer in self.layers:
            h = layer(h)
        return h


class PanguUltraMoEForCausalLM(nn.Layer):
    # what a family of the same shape (models/deepseek_v32.py) replaces: the
    # attribute the model under the head is kept under, and the two classes
    backbone_name = "pangu"
    model_class = PanguUltraMoEModel
    mtp_class = PanguMTPModule

    def __init__(self, cfg):
        with SetupSpan("model.init", family=type(self).__name__, dtype=cfg.dtype) as span:
            super().__init__()
            self.config = cfg
            setattr(self, self.backbone_name, self.model_class(cfg))
            self.lm_head = _Dense(cfg.hidden_size, cfg.vocab_size, cfg.dtype)
            self.mtp = (self.mtp_class(cfg) if cfg.num_nextn_predict_layers > 0 else None)
            span.note(parameters=self.num_params())

    @property
    def backbone(self):
        """The model under the head: embedding, layers, final norm."""
        return getattr(self, self.backbone_name)

    def _head(self, h, gain):
        eps = self.config.rms_norm_eps

        def head(p, x):
            return _rms(x, p["norm"], eps) @ p["head"]

        return _apply(head, {"norm": gain.weight, "head": self.lm_head.weight}, h)

    def forward(self, input_ids, mtp: bool = False):
        """[B, S] ids -> logits [B, S, V].  ``mtp=True`` (a model with the
        next-token module): -> (logits, mtp_logits [B, S - 1, V]), where row t
        of ``mtp_logits``, from the trunk's h_t and the embedding of token
        t + 1, predicts token t + 2."""
        h = self.backbone(input_ids)
        logits = self._head(h, self.backbone.norm)
        if not mtp:
            return logits
        if self.mtp is None:
            raise ValueError("this model was built with num_nextn_predict_layers=0")
        m, eps = self.mtp, self.config.rms_norm_eps

        def mtp_merge(p, h, ids):
            emb = p["embed"][ids[:, 1:]]
            cat = jnp.concatenate([_rms(h[:, :-1], p["hnorm"], eps),
                                   _rms(emb, p["enorm"], eps)], axis=-1)
            return cat @ p["proj"]

        merged = _apply(mtp_merge, {"embed": self.backbone.embed_tokens.weight,
                                    "hnorm": m.hnorm.weight, "enorm": m.enorm.weight,
                                    "proj": m.eh_proj.weight}, h, input_ids)
        return logits, self._head(m.block(merged), m.norm)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ---------------------------------------------- what a serving engine asks
    def serving_weights(self, dtype):
        """The trunk's weight pytree.  ``wkv_b`` is split into the two
        per-head maps the absorbed form multiplies by: ``wuk`` [H, N, C]
        (queries into the latent space) and ``wuv`` [H, C, V] (back out)."""
        cfg = self.config
        H, N, V, C = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                      cfg.kv_lora_rank)

        def v(t):
            return t._value.astype(dtype)

        net = self.backbone
        w = {"embed": v(net.embed_tokens.weight), "norm": v(net.norm.weight),
             "head": v(self.lm_head.weight), "layers": []}
        for layer in net.layers:
            lw = {k: v(t) for k, t in layer.leaves().items()}
            kvb = lw.pop("wkv_b").reshape(C, H, N + V)
            lw["wuk"] = jnp.transpose(kvb[..., :N], (1, 2, 0))
            lw["wuv"] = jnp.transpose(kvb[..., N:], (1, 0, 2))
            w["layers"].append(lw)
        return w

    def serving_cache_spec(self):
        from ..inference.serving_model import CacheSpec

        cfg = self.config
        return CacheSpec(
            arrays=(("latent", lambda bs: (bs, cfg.latent_cache_width)),),
            layers=cfg.num_hidden_layers,
            key=("pangu_ultra_moe", cfg.hidden_size, cfg.num_attention_heads,
                 cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                 cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.n_routed_experts,
                 cfg.num_experts_per_tok, cfg.experts_held, cfg.first_k_dense_replace,
                 float(cfg.routed_scaling_factor), float(cfg.rms_norm_eps)),
            quantizable=False, transferable=False,
            why_not=("a latent (MLA) cache holds one [kv_lora_rank + "
                     "qk_rope_head_dim] entry a token: the int8 scales and the "
                     "block wire format are per kv-head (ROADMAP D3)"))

    def serving_rope(self, max_seq_len):
        return rope_table(self.config, max_seq_len)

    def serving_trunk(self, *, block_size, cache_quant="none"):
        """trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
        scales) -> (hidden [T, E] after the final norm, caches, [], counts):
        packed tokens through every layer against the paged latent cache.
        ``counts``: the names seeded below, which the expert layers fill
        (``_moe_ffn``), and ONE layer's attention's (``latent_counts``)."""
        cfg = self.config
        eps, C = cfg.rms_norm_eps, cfg.kv_lora_rank
        scale = cfg.qk_head_dim ** -0.5
        pad = cfg.latent_cache_width - cfg.latent_width

        def trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
                  scales=None):
            (lat,) = caches
            T, B = token_ids.shape[0], bt.shape[0]
            tok = jnp.arange(T, dtype=jnp.int32)
            b_idx = jnp.clip(
                jnp.searchsorted(cu, tok, side="right").astype(jnp.int32) - 1, 0, B - 1)
            local = tok - cu[b_idx]
            valid = (tok < cu[-1]) & (local < now[b_idx])
            pos = jnp.clip(dec[b_idx] + local, 0, rope.shape[1] - 1)
            cos, sin = rope[0, pos], rope[1, pos]
            with jax.named_scope("embed"):
                hidden = weights["embed"][token_ids]
            counts = {name: jnp.zeros((), jnp.int32) for name in (
                "moe_tokens", "moe_local_picks", "expert_rows_grouped")}
            counts.update(latent_counts(hidden.dtype, lat[0], now, bt,
                                        heads=cfg.num_attention_heads, rank=C))
            for li, lw in enumerate(weights["layers"]):
                with jax.named_scope("norm"):
                    h = _rms(hidden, lw["ln_in"], eps)
                with jax.named_scope("latent_proj"):
                    q_n, q_r, c, k_r = _latent_proj(cfg, lw, h, cos, sin)
                    q_lat = jnp.einsum("thn,hnc->thc", q_n, lw["wuk"])
                    q = jnp.pad(jnp.concatenate([q_lat, q_r], axis=-1),
                                ((0, 0), (0, 0), (0, pad)))
                    entries = jnp.pad(jnp.concatenate([c, k_r], axis=-1),
                                      ((0, 0), (0, pad)))
                o_lat, lat[li] = latent_attention(
                    q, entries, lat[li], dec, now, cu, bt, rank=C, max_q_len=mq,
                    scale=scale)
                with jax.named_scope("attn_out"):
                    o = jnp.einsum("thc,hcv->thv", o_lat, lw["wuv"])
                    attn = o.reshape(T, -1) @ lw["wo"]
                with jax.named_scope("post_norm"):
                    hidden = hidden + _rms(attn, lw["ln_post_attn"], eps)
                with jax.named_scope("norm"):
                    h2 = _rms(hidden, lw["ln_pre_mlp"], eps)
                if "router" in lw:
                    ffn, _ = _moe_ffn(cfg, lw, h2, valid, counts=counts)
                else:
                    with jax.named_scope("mlp"):
                        ffn = _swiglu(h2, lw["wg"], lw["wu"], lw["wd"])
                with jax.named_scope("post_norm"):
                    hidden = hidden + _rms(ffn, lw["ln_post_mlp"], eps)
            with jax.named_scope("norm"):
                hidden = _rms(hidden, weights["norm"], eps)
            return hidden, (lat,), [], counts

        return trunk
