"""LFM2-MoE (``model_type`` ``lfm2_moe``; LFM2-24B-A2B): a decoder whose layers
are mostly GATED SHORT CONVOLUTIONS (``layer_types`` ``conv``: a 3-tap
depthwise causal filter between two gates, no positions, a state of two
inputs a layer) with a grouped-query attention layer every fourth
(``full_attention``: RMSNorm over each query and key head, RoPE), two leading
dense SwiGLU layers and then sigmoid-routed experts chosen on score + bias,
with NO shared expert.  Published config:
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json
whose key names ``Lfm2MoeConfig`` keeps.

Layer (x: [T, E]; ``n`` an RMSNorm with a gain):
    h = x + op(n_operator(x));  y = h + ffn(n_ffn(h));  a final n before the head
conv op: [B, C, u] = split3(x W_in);  v = B * u;
    c_t = sum_{j < 3} k[:, j] * v_{t-2+j}  (a channel, zero before position 0);
    out = (C * c) W_out.  Decoding keeps v at the two positions before the
    current one: ``[2, E]`` a layer a sequence, whatever the context's length.
attention op: q, k, v = x W_q, x W_k, x W_v a head; q = n_q(q), k = n_k(k) over
    each head's ``head_dim``; RoPE (rotate-half, ``rope_theta``) on q and k;
    causal softmax(q.k / sqrt(head_dim)) v, ``num_key_value_heads`` shared by
    groups of query heads; W_o.  No bias.
expert ffn: s = sigmoid(float32(x) float32(W_r)); I = top-k(s + b) (b the
    ``expert_bias``, a float32 weight, with ``use_expert_bias``); w_i = s_i /
    (sum_{j in I} s_j + 1e-6) * routed_scaling_factor;
    ffn(x) = sum_{i in I, i held} w_i SwiGLU_i(x).

The expert layer is models/pangu_moe.py's (``_moe_ffn`` over ops/held_experts.py,
the shared expert left out where a layer has none; ``_swiglu``, ``_rms``):
``experts_held`` means here what it means there, and defaults to all.

Two forms of the same mathematics: ``forward`` (whole sequences: the
convolution as two shifts, attention under a causal mask) and
``serving_trunk`` (packed tokens against the engine's paged K/V pool for the
attention layers, ops/paged_attention.py, and its STATE A SLOT for the conv
layers, ops/short_conv.py and inference/serving_model.py)."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import Constant, Normal
from ..ops.dispatch import apply
from ..ops.latent_attention import rope_half, token_coords
from ..ops.short_conv import short_conv
from ..profiler import SetupSpan
from .pangu_moe import F32, HIGHEST, _apply, _Dense, _Gain, _moe_ffn, _rms, _swiglu

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM", "lfm2_moe_tiny", "route_biased"]

_PERIOD = ("full_attention", "conv", "conv", "conv")


def _layer_types(n, dense=2):
    """``dense`` leading conv layers, then attention, conv, conv, conv."""
    return ["conv"] * dense + [_PERIOD[i % 4] for i in range(n - dense)]


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    layer_types: Optional[list] = None          # None: the published pattern
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": 1000000, "rope_type": "default"})
    tie_word_embeddings: bool = True
    dtype: str = "float32"
    # the routed experts this chip holds, [lo, hi) of num_experts; None: all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = _layer_types(self.num_hidden_layers, self.num_dense_layers)
        self.layer_types = list(self.layer_types)[:self.num_hidden_layers]
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {"conv", "full_attention"}):
            raise ValueError(f"layer_types names {len(self.layer_types)} layers of kinds "
                             f"{sorted(set(self.layer_types))} for num_hidden_layers="
                             f"{self.num_hidden_layers} of 'conv' / 'full_attention'")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held={self.experts_held} is no range of "
                             f"{self.num_experts} routed experts")
        self.experts_held = (lo, hi)
        if (self.conv_bias or not self.norm_topk_prob or not self.use_expert_bias
                or self.conv_L_cache < 2
                or self.rope_parameters.get("rope_type", "default") != "default"
                or self.hidden_size % self.num_attention_heads
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("lfm2_moe as published: a convolution without bias, top-k "
                             "weights normalised, experts chosen on score + expert_bias, "
                             "default rope, heads that divide the hidden size")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])

    def is_sparse(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    def layers_of(self, kind: str) -> list:
        return [i for i, t in enumerate(self.layer_types) if t == kind]


def lfm2_moe_tiny(**kw) -> Lfm2MoeConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_hidden_layers=6, num_dense_layers=2,
                num_attention_heads=4, num_key_value_heads=2, num_experts=8,
                num_experts_per_tok=2, max_position_embeddings=256,
                rope_parameters={"rope_theta": 10000.0, "rope_type": "default"})
    base.update(kw)
    return Lfm2MoeConfig(**base)


# ------------------------------------------------------------ the mathematics
def rope_table(cfg, length):
    """[2, length, D/2] float32 (cos, sin): rotate-half, no scaling."""
    d = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    fr = np.outer(np.arange(length, dtype=np.float64), inv)
    return jnp.asarray(np.stack([np.cos(fr), np.sin(fr)]), F32)


def route_biased(x, w_router, bias, top_k, scale):
    """Sigmoid scores in float32 over every routed expert; the CHOICE is the
    ``top_k`` of score + bias, the WEIGHTS the chosen scores without the bias
    over (their sum + 1e-6), scaled.  -> (idx [T, k] int32, w [T, k] float32)."""
    with jax.named_scope("router"):
        g = jax.nn.sigmoid(jnp.dot(x.astype(F32), w_router.astype(F32), precision=HIGHEST))
        _, idx = jax.lax.top_k(g + bias.astype(F32), top_k)
        gv = jnp.take_along_axis(g, idx, axis=-1)
        return idx.astype(jnp.int32), scale * gv / (jnp.sum(gv, -1, keepdims=True) + 1e-6)


def _router_of(p):
    """The ``router=`` of ``pangu_moe._moe_ffn`` for a layer's weights."""
    def router(x, w_router, top_k, scale):
        return route_biased(x, w_router, p["router_bias"], top_k, scale)
    return router


def _gate_in(p, x):
    """x [T, E] -> (v = B * u, C), the convolution's input and its output gate."""
    b, c, u = jnp.split(x @ p["w_in"], 3, axis=-1)
    return b * u, c


def _conv_full(p, x):
    """One sequence [S, E]: the filter as shifts of the whole sequence."""
    v, c = _gate_in(p, x)
    taps = p["conv_k"].shape[1]
    vf = v.astype(F32)
    out = sum(jnp.pad(vf, ((back, 0), (0, 0)))[:vf.shape[0]]
              * p["conv_k"][:, taps - 1 - back].astype(F32) for back in range(taps))
    return (c * out.astype(x.dtype)) @ p["w_out"]


def _qkv(cfg, p, x):
    """x [T, E] -> q [T, H, D], k, v [T, KV, D], the head norms applied."""
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _rms((x @ p["wq"]).reshape(-1, H, D), p["q_norm"], cfg.norm_eps)
    k = _rms((x @ p["wk"]).reshape(-1, KV, D), p["k_norm"], cfg.norm_eps)
    return q, k, (x @ p["wv"]).reshape(-1, KV, D)


def _gqa_full(cfg, p, x):
    """One sequence [S, E], causal, keys and values repeated a group."""
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    S = x.shape[0]
    rope = rope_table(cfg, S)
    q, k, v = _qkv(cfg, p, x)
    q, k = rope_half(q, rope[0], rope[1]), rope_half(k, rope[0], rope[1])
    q = q.reshape(S, KV, H // KV, D)
    s = jnp.einsum("qkgd,skd->kgqs", q, k, preferred_element_type=F32) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1).astype(x.dtype), v)
    return o.reshape(S, H * D) @ p["wo"]


def _ffn(cfg, p, x, valid=None, counts=None):
    """The layer's feed-forward: dense SwiGLU, or the held experts' part of
    the routed result.  -> (y [T, E], picks on a held expert or None)."""
    if "router" not in p:
        with jax.named_scope("mlp"):
            return _swiglu(x, p["wg"], p["wu"], p["wd"]), None
    return _moe_ffn(cfg, p, x, valid, router=_router_of(p), counts=counts)


def _layer_full(cfg, p, x):
    """One decoder layer over sequences x [B, S, E]."""
    op = _conv_full if "w_in" in p else (lambda p, seq: _gqa_full(cfg, p, seq))
    h = x + jax.vmap(lambda seq: op(p, seq))(_rms(x, p["ln_op"], cfg.norm_eps))
    y, _ = _ffn(cfg, p, _rms(h, p["ln_ffn"], cfg.norm_eps).reshape(-1, x.shape[-1]))
    return h + y.reshape(x.shape)


# ------------------------------------------------------------------ the layers
class Lfm2MoeDecoderLayer(nn.Layer):
    """``kind`` 'conv' or 'full_attention'; ``sparse``: experts, else dense."""

    def __init__(self, cfg: Lfm2MoeConfig, kind: str, sparse: bool):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        e, dt, d = cfg.hidden_size, cfg.dtype, cfg.head_dim
        self.operator_norm = _Gain(e, dt)
        self.ffn_norm = _Gain(e, dt)
        if kind == "conv":
            self.conv = nn.Layer()
            self.conv.in_proj = _Dense(e, 3 * e, dt)
            self.conv.conv = self.conv.create_parameter(
                [e, cfg.conv_L_cache], dtype=dt,
                default_initializer=Normal(0.0, cfg.conv_L_cache ** -0.5))
            self.conv.out_proj = _Dense(e, e, dt)
        else:
            a = self.self_attn = nn.Layer()
            a.q_proj = _Dense(e, cfg.num_attention_heads * d, dt)
            a.k_proj = _Dense(e, cfg.num_key_value_heads * d, dt)
            a.v_proj = _Dense(e, cfg.num_key_value_heads * d, dt)
            a.out_proj = _Dense(cfg.num_attention_heads * d, e, dt)
            a.q_layernorm = _Gain(d, dt)
            a.k_layernorm = _Gain(d, dt)
        f = self.feed_forward = nn.Layer()
        if sparse:
            lo, hi = cfg.experts_held
            fm = cfg.moe_intermediate_size
            f.gate = _Dense(e, cfg.num_experts, dt)
            f.expert_bias = f.create_parameter([cfg.num_experts], dtype="float32",
                                               default_initializer=Constant(0.0))
            f.experts_gate = f.create_parameter(
                [hi - lo, e, fm], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
            f.experts_up = f.create_parameter(
                [hi - lo, e, fm], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
            f.experts_down = f.create_parameter(
                [hi - lo, fm, e], dtype=dt, default_initializer=Normal(0.0, fm ** -0.5))
        else:
            f.w1 = _Dense(e, cfg.intermediate_size, dt)
            f.w3 = _Dense(e, cfg.intermediate_size, dt)
            f.w2 = _Dense(cfg.intermediate_size, e, dt)

    def leaves(self):
        out = {"ln_op": self.operator_norm.weight, "ln_ffn": self.ffn_norm.weight}
        if self.kind == "conv":
            c = self.conv
            out.update(w_in=c.in_proj.weight, conv_k=c.conv, w_out=c.out_proj.weight)
        else:
            a = self.self_attn
            out.update(wq=a.q_proj.weight, wk=a.k_proj.weight, wv=a.v_proj.weight,
                       wo=a.out_proj.weight, q_norm=a.q_layernorm.weight,
                       k_norm=a.k_layernorm.weight)
        f = self.feed_forward
        if hasattr(f, "gate"):
            out.update(router=f.gate.weight, router_bias=f.expert_bias, eg=f.experts_gate,
                       eu=f.experts_up, ed=f.experts_down)
        else:
            out.update(wg=f.w1.weight, wu=f.w3.weight, wd=f.w2.weight)
        return out

    def forward(self, x):
        cfg = self.cfg

        def lfm2_layer(p, x):
            return _layer_full(cfg, p, x)

        return _apply(lfm2_layer, self.leaves(), x)


class Lfm2MoeModel(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Layer()
        self.embed_tokens.weight = self.embed_tokens.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(0.0, 1.0))
        self.layers = nn.LayerList([Lfm2MoeDecoderLayer(cfg, kind, cfg.is_sparse(i))
                                    for i, kind in enumerate(cfg.layer_types)])
        self.embedding_norm = _Gain(cfg.hidden_size, cfg.dtype)

    def forward(self, input_ids):
        """[B, S] ids -> the last layer's output [B, S, E], before the norm."""
        h = apply(lambda w, ids: w[ids], self.embed_tokens.weight, input_ids,
                  op_name="embedding")
        for layer in self.layers:
            h = layer(h)
        return h


class Lfm2MoeForCausalLM(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        with SetupSpan("model.init", family=type(self).__name__, dtype=cfg.dtype) as span:
            super().__init__()
            self.config = cfg
            self.model = Lfm2MoeModel(cfg)
            # tied (the LFM2 family's published configs): the head is the
            # embedding table, transposed
            self.lm_head = (None if cfg.tie_word_embeddings
                            else _Dense(cfg.hidden_size, cfg.vocab_size, cfg.dtype))
            span.note(parameters=self.num_params())

    def forward(self, input_ids):
        """[B, S] ids -> logits [B, S, V]."""
        eps, tied = self.config.norm_eps, self.lm_head is None
        net = self.model

        def head(p, x):
            return _rms(x, p["norm"], eps) @ (p["head"].T if tied else p["head"])

        w = net.embed_tokens.weight if tied else self.lm_head.weight
        return _apply(head, {"norm": net.embedding_norm.weight, "head": w}, net(input_ids))

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ---------------------------------------------- what a serving engine asks
    def serving_weights(self, dtype):
        """The trunk's weight pytree; ``expert_bias`` stays float32, as
        published.  A tied head is a transposed COPY of the table (the
        engine's ``"head"`` leaf is ``[hidden, vocab]``)."""
        def v(t):
            return t._value.astype(dtype)

        net = self.model
        w = {"embed": v(net.embed_tokens.weight), "norm": v(net.embedding_norm.weight)}
        w["head"] = w["embed"].T if self.lm_head is None else v(self.lm_head.weight)
        w["layers"] = []
        for layer in net.layers:
            lw = {k: v(t) for k, t in layer.leaves().items()}
            if "router_bias" in lw:
                lw["router_bias"] = layer.feed_forward.expert_bias._value
            w["layers"].append(lw)
        return w

    def serving_cache_spec(self):
        """Keys and values a kv-head for the ATTENTION layers alone, heads of
        64 two to a lane tile (``lane_packing``: a block ``[KV / 2, bs, 128]``,
        which both paged kernels take as it lies), and the conv layers' state
        a slot: the ``conv_L_cache - 1`` inputs before a sequence's next
        position, ``[L - 1, hidden]`` a layer."""
        from ..inference.serving_model import CacheSpec
        from ..ops.paged_attention import lane_packing

        cfg = self.config
        KV, D = cfg.num_key_value_heads, cfg.head_dim
        state = (("conv", len(cfg.layers_of("conv")),
                  (cfg.conv_L_cache - 1, cfg.hidden_size)),)
        _, block = lane_packing(KV, D)

        return CacheSpec(
            arrays=(("k", block), ("v", block)), layers=len(cfg.layers_of("full_attention")),
            key=("lfm2_moe", cfg.hidden_size, cfg.num_attention_heads, KV, D,
                 tuple(cfg.layer_types), cfg.num_dense_layers, cfg.num_experts,
                 cfg.num_experts_per_tok, cfg.experts_held,
                 float(cfg.routed_scaling_factor), float(cfg.norm_eps), state),
            kv_heads=KV, head_dim=D, quantizable=False, transferable=False,
            slot_state=state,
            why_not=("its conv layers keep STATE A SLOT (the last conv_L_cache - 1 inputs "
                     "of a sequence, no positions) beside the attention layers' blocks: "
                     "a block carries no such state, so a request that adopts cached or "
                     "imported blocks would start at position n without the state of "
                     "n - 1, a refused draft would have advanced it, and the int8 "
                     "scales know nothing of it (ROADMAP A4)"))

    def serving_rope(self, max_seq_len):
        # blha's layout [2, Br=1, Smax, 1, D/2]
        return rope_table(self.config, max_seq_len)[:, None, :, None, :]

    def serving_trunk(self, *, block_size, cache_quant="none"):
        """trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
        scales) -> (hidden [T, E] after the final norm, caches, [], counts):
        packed tokens through every layer; ``caches`` = (key pools, value
        pools: an attention layer each; conv state ``[conv layers, B, L - 1,
        E]``).  ``counts``: ``conv_rows_fed`` (row-layers whose state
        advanced), the expert layers' seven seeded below (``_moe_ffn``,
        ``held_experts``) and ONE attention layer's six (``paged_counts``)."""
        from ..ops.paged_attention import blha_attention, paged_counts

        cfg = self.config
        H, KV, D, eps = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                         cfg.norm_eps)
        cache_of = {l: i for i, l in enumerate(cfg.layers_of("full_attention"))}
        state_of = {l: i for i, l in enumerate(cfg.layers_of("conv"))}

        def trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
                  scales=None):
            key_caches, value_caches, conv_state = caches
            T, B = token_ids.shape[0], bt.shape[0]
            row, pos, valid = token_coords(T, dec, now, cu, B)
            with jax.named_scope("embed"):
                hidden = weights["embed"][token_ids]
            counts = {name: jnp.zeros((), jnp.int32) for name in (
                "conv_rows_fed", "moe_tokens", "moe_local_picks", "experts_touched",
                "expert_tiles", "expert_tile_rows", "expert_tile_rows_live",
                "expert_rows_grouped")}
            for li, lw in enumerate(weights["layers"]):
                with jax.named_scope("norm"):
                    h = _rms(hidden, lw["ln_op"], eps)
                if li in state_of:
                    with jax.named_scope("conv_proj"):
                        v, gate = _gate_in(lw, h)
                    c, state = short_conv(v, lw["conv_k"], conv_state[state_of[li]],
                                          row, pos, dec, now, cu)
                    conv_state = conv_state.at[state_of[li]].set(state)
                    counts["conv_rows_fed"] += jnp.sum(now > 0).astype(jnp.int32)
                    with jax.named_scope("conv_out"):
                        hidden = hidden + (gate * c) @ lw["w_out"]
                else:
                    with jax.named_scope("attn_proj"):
                        q, k, v = _qkv(cfg, lw, h)
                        qkv = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                                               v.reshape(T, -1)], axis=-1)
                    ci = cache_of[li]
                    with jax.named_scope("attention"):
                        out, key_caches[ci], value_caches[ci], *_ = blha_attention(
                            qkv, key_caches[ci], value_caches[ci], enc, dec, now, cu, bt,
                            num_heads=H, kv_num_heads=KV, head_dim=D, block_size=block_size,
                            max_q_len=mq, use_neox_style=True, compute_dtype=hidden.dtype,
                            rope_emb=rope)
                    with jax.named_scope("attn_out"):
                        hidden = hidden + out @ lw["wo"]
                with jax.named_scope("norm"):
                    h2 = _rms(hidden, lw["ln_ffn"], eps)
                ffn, _ = _ffn(cfg, lw, h2, valid, counts)
                hidden = hidden + ffn
            with jax.named_scope("norm"):
                hidden = _rms(hidden, weights["norm"], eps)
            counts.update(paged_counts(hidden.dtype, key_caches[0], dec, now, cu, bt, tokens=T,
                                       heads=H, max_q_len=mq))
            return hidden, (key_caches, value_caches, conv_state), [], counts

        return trunk
