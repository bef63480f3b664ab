"""SmallThinker (``model_name`` ``smallthinker_21b_instruct``;
SmallThinker-21BA3B-Instruct): a decoder whose attention layers are of TWO
kinds by a published layout of period four, ``sliding_window_layout`` ==
``rope_layout`` == ``0, 1, 1, 1``: a layer with 0 is GLOBAL and has NO position
encoding (NoPE), a layer with 1 attends the last ``sliding_window_size``
positions and has RoPE; every layer's feed-forward is 64 ReGLU experts of
which a token takes 6, chosen by a router that reads the ATTENTION's input.
Published config:
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json
whose key names ``SmallThinkerConfig`` keeps.

Layer (x: [T, E]; ``n`` an RMSNorm with a gain):
    a = n_in(x);  r = float32(a) float32(W_r)   (the router, BEFORE attention);
    h = x + Attn(a);  y = h + MoE(n_post(h); r);  a final n before the head
Attn: grouped-query, ``num_attention_heads`` query heads over
    ``num_key_value_heads`` key/value heads of ``head_dim`` (the heads' width is
    not the hidden size), no bias, no head norms, softmax at head_dim**-0.5.
    Window layer: rotate-half RoPE (``rope_theta``) on q and k; the query at t
    attends keys ``t - window + 1 .. t``.  Global layer: q and k as projected;
    the query at t attends ``0 .. t``.
MoE(u; r): I = the ``moe_num_active_primary_experts`` largest of r's
    ``moe_num_primary_experts`` logits; w = softmax over THOSE in float32;
    MoE = sum_{i in I, i held} w_i W_down_i(relu(W_gate_i u) * (W_up_i u)).
    No shared expert.

The expert layer is ops/held_experts.py's (``activation="relu"``) under
``pangu_moe.route_chosen``; ``experts_held`` means what it means in
models/pangu_moe.py and defaults to all.

Two forms of the same mathematics: ``forward`` (whole sequences under an
explicit mask) and ``serving_trunk`` (packed tokens against the engine's paged
K/V pools, one a KIND of layer: the global layers' keeps every position, the
window layers' gives back the blocks behind the window while a row runs;
inference/serving_model.py ``CacheSpec.kinds``, ops/paged_attention.py
``window=``)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import Normal
from ..ops.dispatch import apply
from ..ops.held_experts import held_experts
from ..ops.latent_attention import rope_half, token_coords
from ..profiler import SetupSpan
from .pangu_moe import F32, HIGHEST, _apply, _Dense, _Gain, _rms, route_chosen

__all__ = ["SmallThinkerConfig", "SmallThinkerModel", "SmallThinkerForCausalLM",
           "smallthinker_tiny"]

_PERIOD = (0, 1, 1, 1)


@dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    head_dim: int = 128
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    sliding_window_size: int = 4096
    sliding_window_layout: Optional[list] = None    # None: the published period
    rope_layout: Optional[list] = None
    rope_theta: float = 1500000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    # the experts this chip holds, [lo, hi) of moe_num_primary_experts; None: all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("sliding_window_layout", "rope_layout"):
            got = getattr(self, name)
            got = [_PERIOD[i % 4] for i in range(n)] if got is None else list(got)[:n]
            if len(got) != n or set(got) - {0, 1}:
                raise ValueError(f"{name} names {len(got)} layers for num_hidden_layers={n}, "
                                 "each 0 or 1")
            setattr(self, name, got)
        if self.experts_held is None:
            self.experts_held = (0, self.moe_num_primary_experts)
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.moe_num_primary_experts:
            raise ValueError(f"experts_held={self.experts_held} is no range of "
                             f"{self.moe_num_primary_experts} experts")
        self.experts_held = (lo, hi)
        if (not self.moe_primary_router_apply_softmax or self.rope_scaling
                or self.tie_word_embeddings or self.sliding_window_size < 1
                or self.num_attention_heads % self.num_key_value_heads
                or 0 not in self.sliding_window_layout[:1]):
            raise ValueError("smallthinker as published: a softmax router, rope without "
                             "scaling, an untied head, query heads a multiple of the "
                             "key/value heads, a first layer that is global")

    def windowed(self, layer: int) -> bool:
        return bool(self.sliding_window_layout[layer])

    def roped(self, layer: int) -> bool:
        return bool(self.rope_layout[layer])

    def layers_of(self, windowed: bool) -> list:
        return [i for i in range(self.num_hidden_layers) if self.windowed(i) == windowed]


def smallthinker_tiny(**kw) -> SmallThinkerConfig:
    base = dict(vocab_size=256, hidden_size=64, head_dim=16, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, moe_ffn_hidden_size=32,
                moe_num_primary_experts=8, moe_num_active_primary_experts=2,
                sliding_window_size=24, rope_theta=10000.0, max_position_embeddings=256)
    base.update(kw)
    return SmallThinkerConfig(**base)


# ------------------------------------------------------------ the mathematics
def rope_table(cfg, length):
    """[2, length, D/2] float32 (cos, sin): rotate-half over the whole head, no scaling."""
    d = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    fr = np.outer(np.arange(length, dtype=np.float64), inv)
    return jnp.asarray(np.stack([np.cos(fr), np.sin(fr)]), F32)


def router_logits(a, w_router):
    """The layer's router over the attention's INPUT ``a`` (already normed), float32."""
    with jax.named_scope("router"):
        return jnp.dot(a.astype(F32), w_router.astype(F32), precision=HIGHEST)


def _experts(cfg, p, u, logits, valid=None, counts=None):
    """The held experts' part of MoE(u; r). -> y in u's dtype."""
    idx, w = route_chosen(logits, cfg.moe_num_active_primary_experts)
    y, picks = held_experts(u, idx, w, p["eg"], p["eu"], p["ed"], cfg.experts_held[0],
                            valid, counts=counts, activation="relu", routed=logits.shape[-1])
    if counts is not None:
        counts["moe_tokens"] += (jnp.sum(valid).astype(jnp.int32) if valid is not None
                                 else u.shape[0])
        counts["moe_local_picks"] += picks
    return y.astype(u.dtype)


def _attn_full(cfg, p, a, layer):
    """One sequence [S, E] (normed) under an explicit [S, S] mask."""
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    S = a.shape[0]
    q = (a @ p["wq"]).reshape(S, H, D)
    k = (a @ p["wk"]).reshape(S, KV, D)
    v = (a @ p["wv"]).reshape(S, KV, D)
    if cfg.roped(layer):
        rope = rope_table(cfg, S)
        q, k = rope_half(q, rope[0], rope[1]), rope_half(k, rope[0], rope[1])
    at = jnp.arange(S)
    mask = at[None, :] <= at[:, None]
    if cfg.windowed(layer):
        mask = mask & (at[None, :] > at[:, None] - cfg.sliding_window_size)
    s = jnp.einsum("qkgd,skd->kgqs", q.reshape(S, KV, H // KV, D), k,
                   preferred_element_type=F32) * D ** -0.5
    s = jnp.where(mask[None, None], s, -1e30)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1).astype(a.dtype), v)
    return o.reshape(S, H * D) @ p["wo"]


def _layer_full(cfg, p, x, layer):
    """One decoder layer over sequences x [B, S, E]."""
    a = _rms(x, p["ln_in"], cfg.rms_norm_eps)
    r = router_logits(a.reshape(-1, x.shape[-1]), p["router"])
    h = x + jax.vmap(lambda seq: _attn_full(cfg, p, seq, layer))(a)
    u = _rms(h, p["ln_post"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    return h + _experts(cfg, p, u, r).reshape(x.shape)


# ------------------------------------------------------------------ the layers
class SmallThinkerDecoderLayer(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig, layer: int):
        super().__init__()
        self.cfg, self.layer = cfg, layer
        e, dt, d = cfg.hidden_size, cfg.dtype, cfg.head_dim
        self.input_layernorm = _Gain(e, dt)
        self.post_attention_layernorm = _Gain(e, dt)
        a = self.self_attn = nn.Layer()
        a.q_proj = _Dense(e, cfg.num_attention_heads * d, dt)
        a.k_proj = _Dense(e, cfg.num_key_value_heads * d, dt)
        a.v_proj = _Dense(e, cfg.num_key_value_heads * d, dt)
        a.o_proj = _Dense(cfg.num_attention_heads * d, e, dt)
        f = self.block_sparse_moe = nn.Layer()
        lo, hi = cfg.experts_held
        fm = cfg.moe_ffn_hidden_size
        f.primary_router = _Dense(e, cfg.moe_num_primary_experts, dt)
        f.experts_gate = f.create_parameter(
            [hi - lo, e, fm], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
        f.experts_up = f.create_parameter(
            [hi - lo, e, fm], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
        f.experts_down = f.create_parameter(
            [hi - lo, fm, e], dtype=dt, default_initializer=Normal(0.0, fm ** -0.5))

    def leaves(self):
        a, f = self.self_attn, self.block_sparse_moe
        return {"ln_in": self.input_layernorm.weight,
                "ln_post": self.post_attention_layernorm.weight,
                "wq": a.q_proj.weight, "wk": a.k_proj.weight, "wv": a.v_proj.weight,
                "wo": a.o_proj.weight, "router": f.primary_router.weight,
                "eg": f.experts_gate, "eu": f.experts_up, "ed": f.experts_down}

    def forward(self, x):
        cfg, layer = self.cfg, self.layer

        def smallthinker_layer(p, x):
            return _layer_full(cfg, p, x, layer)

        return _apply(smallthinker_layer, self.leaves(), x)


class SmallThinkerModel(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Layer()
        self.embed_tokens.weight = self.embed_tokens.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(0.0, 1.0))
        self.layers = nn.LayerList([SmallThinkerDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Gain(cfg.hidden_size, cfg.dtype)

    def forward(self, input_ids):
        """[B, S] ids -> the last layer's output [B, S, E], before the norm."""
        h = apply(lambda w, ids: w[ids], self.embed_tokens.weight, input_ids,
                  op_name="embedding")
        for layer in self.layers:
            h = layer(h)
        return h


class SmallThinkerForCausalLM(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        with SetupSpan("model.init", family=type(self).__name__, dtype=cfg.dtype) as span:
            super().__init__()
            self.config = cfg
            self.model = SmallThinkerModel(cfg)
            self.lm_head = _Dense(cfg.hidden_size, cfg.vocab_size, cfg.dtype)
            span.note(parameters=self.num_params())

    def forward(self, input_ids):
        """[B, S] ids -> logits [B, S, V]."""
        eps = self.config.rms_norm_eps

        def head(p, x):
            return _rms(x, p["norm"], eps) @ p["head"]

        return _apply(head, {"norm": self.model.norm.weight, "head": self.lm_head.weight},
                      self.model(input_ids))

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ---------------------------------------------- what a serving engine asks
    def serving_weights(self, dtype):
        def v(t):
            return t._value.astype(dtype)

        net = self.model
        return {"embed": v(net.embed_tokens.weight), "norm": v(net.norm.weight),
                "head": v(self.lm_head.weight),
                "layers": [{k: v(t) for k, t in layer.leaves().items()}
                           for layer in net.layers]}

    def serving_cache_spec(self):
        """Keys and values a kv-head a layer, in TWO kinds of cache layer: the
        global layers' (every position) first, then the window layers', which
        keep a row's last ``sliding_window_size`` positions."""
        from ..inference.serving_model import CacheKind, CacheSpec

        cfg = self.config
        KV, D = cfg.num_key_value_heads, cfg.head_dim
        kinds = (CacheKind("global", len(cfg.layers_of(False))),
                 CacheKind("window", len(cfg.layers_of(True)), cfg.sliding_window_size))
        kinds = tuple(k for k in kinds if k.layers)
        return CacheSpec(
            arrays=(("k", lambda bs: (KV, bs, D)), ("v", lambda bs: (KV, bs, D))),
            layers=cfg.num_hidden_layers,
            key=("smallthinker", cfg.hidden_size, cfg.num_attention_heads, KV, D,
                 tuple(cfg.sliding_window_layout), tuple(cfg.rope_layout),
                 cfg.sliding_window_size, cfg.moe_num_primary_experts,
                 cfg.moe_num_active_primary_experts, cfg.experts_held,
                 float(cfg.rms_norm_eps)),
            kv_heads=KV, head_dim=D, quantizable=False, transferable=False, kinds=kinds,
            why_not=("its window layers GIVE BACK the blocks behind their last "
                     f"{cfg.sliding_window_size} positions while a row runs, so a request's "
                     "blocks are not all its positions: a published or exported prefix has "
                     "lost its window layers' blocks, a refused draft may lie past a block "
                     "already given back, and the int8 scales follow ONE pool's blocks "
                     "(ROADMAP A3)"))

    def serving_rope(self, max_seq_len):
        # blha's layout [2, Br=1, Smax, 1, D/2]
        return rope_table(self.config, max_seq_len)[:, None, :, None, :]

    def serving_trunk(self, *, block_size, cache_quant="none"):
        """trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
        scales) -> (hidden [T, E] after the final norm, caches, [], counts):
        packed tokens through every layer; ``caches`` = (key pools, value pools),
        the global layers' first, then the window layers'; ``bt`` a table a
        kind in that order (ONE table where the config has one kind).
        ``counts``: the expert layers' seven (``held_experts``), ONE global
        layer's six (``paged_counts``), and by kind ONE layer's
        ``attn_positions_live.<kind>`` / ``attn_positions_read.<kind>`` /
        ``attn_chunks_kernel.<kind>`` with
        ``window_positions_spared``: the live context behind the first key a
        row's first query attends, which ONE window layer did not read."""
        from ..ops.paged_attention import first_key, blha_attention, paged_counts

        cfg = self.config
        H, KV, D, eps = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                         cfg.rms_norm_eps)
        order = cfg.layers_of(False) + cfg.layers_of(True)
        cache_of = {l: i for i, l in enumerate(order)}
        two = bool(cfg.layers_of(False)) and bool(cfg.layers_of(True))
        W = cfg.sliding_window_size

        def trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
                  scales=None):
            key_caches, value_caches = caches
            tables = bt if two else (bt, bt)
            T, B = token_ids.shape[0], tables[0].shape[0]
            _, _, valid = token_coords(T, dec, now, cu, B)
            with jax.named_scope("embed"):
                hidden = weights["embed"][token_ids]
            counts = {name: jnp.zeros((), jnp.int32) for name in (
                "moe_tokens", "moe_local_picks", "experts_touched", "expert_tiles",
                "expert_tile_rows", "expert_tile_rows_live", "expert_rows_grouped")}
            for li, lw in enumerate(weights["layers"]):
                windowed = cfg.windowed(li)
                with jax.named_scope("norm"):
                    a = _rms(hidden, lw["ln_in"], eps)
                logits = router_logits(a, lw["router"])   # BEFORE attention, from its input
                with jax.named_scope("attn_proj"):
                    qkv = jnp.concatenate([a @ lw["wq"], a @ lw["wk"], a @ lw["wv"]], axis=-1)
                ci = cache_of[li]
                with jax.named_scope("attention"):
                    out, key_caches[ci], value_caches[ci], *_ = blha_attention(
                        qkv, key_caches[ci], value_caches[ci], enc, dec, now, cu,
                        tables[windowed], num_heads=H, kv_num_heads=KV, head_dim=D,
                        block_size=block_size, max_q_len=mq, use_neox_style=True,
                        compute_dtype=hidden.dtype,
                        rope_emb=rope if cfg.roped(li) else None,
                        window=W if windowed else None)
                with jax.named_scope("attn_out"):
                    hidden = hidden + out @ lw["wo"]
                with jax.named_scope("norm"):
                    u = _rms(hidden, lw["ln_post"], eps)
                hidden = hidden + _experts(cfg, lw, u, logits, valid, counts)
            with jax.named_scope("norm"):
                hidden = _rms(hidden, weights["norm"], eps)
            for kind, windowed in (("global", False), ("window", True)):
                layers = cfg.layers_of(windowed)
                if not layers:
                    continue
                got = paged_counts(hidden.dtype, key_caches[cache_of[layers[0]]], dec, now, cu,
                                   tables[windowed], tokens=T, heads=H, max_q_len=mq,
                                   window=W if windowed else None)
                if kind == "global":          # the six every paged trunk carries
                    counts.update(got)
                for name in ("attn_positions_live", "attn_positions_read", "attn_chunks_kernel"):
                    counts[f"{name}.{kind}"] = got[name]
            if cfg.layers_of(True):
                counts["window_positions_spared"] = jnp.sum(
                    jnp.where(now > 0, first_key(dec, W), 0)).astype(jnp.int32)
            return hidden, (key_caches, value_caches), [], counts

        return trunk
