"""Command A+ (``model_type`` ``cohere2_moe``; command-a-plus-05-2026,
218B-A25B): a decoder whose layer is a PARALLEL block.  ONE LayerNorm a layer
feeds the attention, four averaged shared experts and 8-of-128 sigmoid-routed
experts; all three join the residual together, so nothing of the feed-forward
waits for the attention's result.  Attention layers are of two kinds by the
published ``layer_types`` of period four (``sliding_attention`` x 3, then
``full_attention``): a sliding layer attends the last ``sliding_window``
positions under RoPE on INTERLEAVED pairs (``rope_gptj``), a full layer attends
everything and has NO position encoding.  The head is the embedding table.
Published config:
https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json
whose key names ``Cohere2MoeConfig`` keeps (the language model alone: the
vision tower has no key in it and is not built).

Layer (x: [T, E]):
    u = LN(x);  y = x + Attn(u) + Shared(u) + Routed(u);  a final LN before the head
LN: LayerNorm WITH the mean taken out, a gain, no bias, in float32
    (``layer_norm_eps``; ops/norms.py ``layer_norm``).
Attn: grouped-query, ``num_attention_heads`` query heads over
    ``num_key_value_heads`` key/value heads of ``head_dim`` (the heads' width,
    16,384, is four times the hidden size), no bias, no head norms, softmax at
    head_dim**-0.5.  Sliding layer: RoPE on q and k, pairs (2i, 2i + 1); the
    query at t attends keys ``t - sliding_window + 1 .. t``.  Full layer: q and k
    as projected; the query at t attends ``0 .. t``.
Routed: s = sigmoid(float32(u) float32(W_r)) over all ``num_experts``; I = the
    ``num_experts_per_tok`` largest; w_i = s_i / sum_{j in I} s_j (no bias on
    the choice, no scaling); Routed = sum_{i in I, i held} w_i SwiGLU_i(u), each
    of width ``intermediate_size``.  ``pangu_moe.route`` at scale 1.
Shared: (1 / ``num_shared_experts``) sum_j SwiGLU^s_j(u), each of width
    ``intermediate_size``: ONE SwiGLU over the four side by side (``sg``, ``su``
    [E, 4F], ``sd`` [4F, E]; expert j is columns / rows ``[jF, (j + 1)F)``)
    whose down product is quartered.
head: logits = LN(h) E^T * ``logit_scale`` (E the embedding table, tied).

``experts_held`` means what it means in models/pangu_moe.py (``num_experts``
stays the router's width); ``vocab_size`` is the vocabulary HELD: a sliced
vocabulary is a smaller one, its table the rows of the slice.

Two forms of the same mathematics: ``forward`` (whole sequences under an
explicit mask) and ``serving_trunk`` (packed tokens against the engine's paged
K/V pools, one a KIND of layer as models/smallthinker.py's; kind ``global``
stays first in ``CacheSpec.kinds`` though layer 0 is a sliding layer)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import Normal
from ..ops.dispatch import apply
from ..ops.held_experts import _swiglu, held_experts
from ..ops.latent_attention import token_coords
from ..ops.paged_attention import rope_rotate
from ..ops.norms import layer_norm
from ..profiler import SetupSpan
from .pangu_moe import F32, _apply, _Dense, _Gain, route
from .smallthinker import rope_table   # (cos, sin) a pair's angle: the pairing is the caller's

__all__ = ["Cohere2MoeConfig", "Cohere2MoeModel", "Cohere2MoeForCausalLM",
           "cohere2_moe_tiny"]

_PERIOD = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention")


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # an expert's width, routed or shared
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Optional[list] = None     # None: the published period
    sliding_window: int = 4096
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    shared_expert_combination_strategy: str = "average"
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 0
    logit_scale: float = 1.0
    layer_norm_eps: float = 1e-5
    position_embedding_type: str = "rope_gptj"
    rotary_pct: float = 1.0
    rope_theta: float = 50000.0
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    use_gated_activation: bool = True
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 200000
    dtype: str = "float32"
    # the routed experts this chip holds, [lo, hi) of num_experts; None: all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        got = ([_PERIOD[i % 4] for i in range(n)] if self.layer_types is None
               else list(self.layer_types)[:n])
        if len(got) != n or set(got) - set(_PERIOD):
            raise ValueError(f"layer_types names {len(got)} layers for num_hidden_layers={n}, "
                             f"each one of {sorted(set(_PERIOD))}")
        self.layer_types = got
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held={self.experts_held} is no range of "
                             f"{self.num_experts} experts")
        self.experts_held = (lo, hi)
        if (not self.use_parallel_block or self.use_qk_norm or not self.use_gated_activation
                or self.shared_expert_combination_strategy != "average"
                or self.expert_selection_fn != "sigmoid" or not self.norm_topk_prob
                or self.first_k_dense_replace > 0 or self.rotary_pct != 1
                or self.position_embedding_type != "rope_gptj" or self.hidden_act != "silu"
                or self.attention_bias or not self.tie_word_embeddings
                or self.num_shared_experts < 1 or self.sliding_window < 1
                or self.num_attention_heads % self.num_key_value_heads
                or "full_attention" not in got):
            raise ValueError("cohere2_moe as published: a parallel block, no head norms, "
                             "SwiGLU experts, shared experts averaged, a sigmoid router "
                             "normalised over the chosen, no dense prefix layer, RoPE on the "
                             "whole head in interleaved pairs (rope_gptj), no bias, a tied "
                             "head, query heads a multiple of the key/value heads, a full layer among "
                             "those built")

    def windowed(self, layer: int) -> bool:
        return self.layer_types[layer] == "sliding_attention"

    def layers_of(self, windowed: bool) -> list:
        return [i for i in range(self.num_hidden_layers) if self.windowed(i) == windowed]


def cohere2_moe_tiny(**kw) -> Cohere2MoeConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=32, num_hidden_layers=4,
                num_attention_heads=8, num_key_value_heads=2, head_dim=16, sliding_window=24,
                num_experts=16, num_experts_per_tok=4, num_shared_experts=4,
                rope_theta=10000.0, max_position_embeddings=256)
    base.update(kw)
    return Cohere2MoeConfig(**base)


# ------------------------------------------------------------ the mathematics
def _shared(cfg, p, u):
    """The shared experts' average: ONE SwiGLU over the four side by side, its
    down product (the four's SUM) divided by their number. -> float32."""
    with jax.named_scope("shared_experts"):
        return _swiglu(u, p["sg"], p["su"], p["sd"]).astype(F32) / cfg.num_shared_experts


def _routed(cfg, p, u, valid=None, counts=None):
    """The held experts' part of Routed(u). -> float32."""
    idx, w = route(u, p["router"], cfg.num_experts_per_tok, 1.0)
    y, picks = held_experts(u, idx, w, p["eg"], p["eu"], p["ed"], cfg.experts_held[0],
                            valid, counts=counts, routed=p["router"].shape[-1])
    if counts is not None:
        counts["moe_local_picks"] += picks
    return y


def _join(x, *parts):
    """The block's ONE residual add, in float32, back in x's dtype."""
    return sum((part.astype(F32) for part in parts), x.astype(F32)).astype(x.dtype)


def _attn_full(cfg, p, u, layer):
    """One sequence [S, E] (normed) under an explicit [S, S] mask."""
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    S = u.shape[0]
    q = (u @ p["wq"]).reshape(S, H, D)
    k = (u @ p["wk"]).reshape(S, KV, D)
    v = (u @ p["wv"]).reshape(S, KV, D)
    at = jnp.arange(S)
    mask = at[None, :] <= at[:, None]
    if cfg.windowed(layer):
        cos, sin = rope_table(cfg, S)[:, :, None, :]
        q, k = rope_rotate(q, cos, sin, neox=False), rope_rotate(k, cos, sin, neox=False)
        mask = mask & (at[None, :] > at[:, None] - cfg.sliding_window)
    s = jnp.einsum("qkgd,skd->kgqs", q.reshape(S, KV, H // KV, D), k,
                   preferred_element_type=F32) * D ** -0.5
    s = jnp.where(mask[None, None], s, -1e30)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1).astype(u.dtype), v)
    return o.reshape(S, H * D) @ p["wo"]


def _layer_full(cfg, p, x, layer):
    """One decoder layer over sequences x [B, S, E]."""
    u = layer_norm(x, p["ln"], None, cfg.layer_norm_eps)
    rows = u.reshape(-1, x.shape[-1])
    attn = jax.vmap(lambda seq: _attn_full(cfg, p, seq, layer))(u)
    return _join(x, attn, _shared(cfg, p, rows).reshape(x.shape),
                 _routed(cfg, p, rows).reshape(x.shape))


# ------------------------------------------------------------------ the layers
class Cohere2MoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: Cohere2MoeConfig, layer: int):
        super().__init__()
        self.cfg, self.layer = cfg, layer
        e, dt, d, f = cfg.hidden_size, cfg.dtype, cfg.head_dim, cfg.intermediate_size
        self.input_layernorm = _Gain(e, dt)
        a = self.self_attn = nn.Layer()
        a.q_proj = _Dense(e, cfg.num_attention_heads * d, dt)
        a.k_proj = _Dense(e, cfg.num_key_value_heads * d, dt)
        a.v_proj = _Dense(e, cfg.num_key_value_heads * d, dt)
        a.o_proj = _Dense(cfg.num_attention_heads * d, e, dt)
        m = self.mlp = nn.Layer()
        lo, hi = cfg.experts_held
        m.gate = _Dense(e, cfg.num_experts, dt)
        m.experts_gate = m.create_parameter(
            [hi - lo, e, f], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
        m.experts_up = m.create_parameter(
            [hi - lo, e, f], dtype=dt, default_initializer=Normal(0.0, e ** -0.5))
        m.experts_down = m.create_parameter(
            [hi - lo, f, e], dtype=dt, default_initializer=Normal(0.0, f ** -0.5))
        # the shared experts side by side: expert j is columns (rows of the
        # down matrix) [j f, (j + 1) f); each down matrix's fan-in is f
        s, fs = nn.Layer(), cfg.num_shared_experts * f
        m.shared_experts = s
        s.gate_proj = _Dense(e, fs, dt)
        s.up_proj = _Dense(e, fs, dt)
        s.down_proj = nn.Layer()
        s.down_proj.weight = s.down_proj.create_parameter(
            [fs, e], dtype=dt, default_initializer=Normal(0.0, f ** -0.5))

    def leaves(self):
        a, m = self.self_attn, self.mlp
        s = m.shared_experts
        return {"ln": self.input_layernorm.weight,
                "wq": a.q_proj.weight, "wk": a.k_proj.weight, "wv": a.v_proj.weight,
                "wo": a.o_proj.weight, "router": m.gate.weight,
                "eg": m.experts_gate, "eu": m.experts_up, "ed": m.experts_down,
                "sg": s.gate_proj.weight, "su": s.up_proj.weight, "sd": s.down_proj.weight}

    def forward(self, x):
        cfg, layer = self.cfg, self.layer

        def cohere2_moe_layer(p, x):
            return _layer_full(cfg, p, x, layer)

        return _apply(cohere2_moe_layer, self.leaves(), x)


class Cohere2MoeModel(nn.Layer):
    def __init__(self, cfg: Cohere2MoeConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Layer()
        self.embed_tokens.weight = self.embed_tokens.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(0.0, cfg.hidden_size ** -0.5))   # it is the head too
        self.layers = nn.LayerList([Cohere2MoeDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Gain(cfg.hidden_size, cfg.dtype)

    def forward(self, input_ids):
        """[B, S] ids -> the last layer's output [B, S, E], before the norm."""
        h = apply(lambda w, ids: w[ids], self.embed_tokens.weight, input_ids,
                  op_name="embedding")
        for layer in self.layers:
            h = layer(h)
        return h


class Cohere2MoeForCausalLM(nn.Layer):
    """The head is the model's own embedding table: there is no second matrix."""

    def __init__(self, cfg: Cohere2MoeConfig):
        with SetupSpan("model.init", family=type(self).__name__, dtype=cfg.dtype) as span:
            super().__init__()
            self.config = cfg
            self.model = Cohere2MoeModel(cfg)
            span.note(parameters=self.num_params())

    def forward(self, input_ids):
        """[B, S] ids -> logits [B, S, V]."""
        eps, scale = self.config.layer_norm_eps, self.config.logit_scale

        def head(p, x):
            return (layer_norm(x, p["norm"], None, eps) @ p["embed"].T) * scale

        return _apply(head, {"norm": self.model.norm.weight,
                             "embed": self.model.embed_tokens.weight}, self.model(input_ids))

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ---------------------------------------------- what a serving engine asks
    def serving_weights(self, dtype):
        """The trunk's weight pytree.  It has NO ``"head"`` leaf: the engine
        heads its rows by the table ``"embed"`` itself (``serving.head_logits``)."""
        def v(t):
            return t._value.astype(dtype)

        net = self.model
        return {"embed": v(net.embed_tokens.weight), "norm": v(net.norm.weight),
                "layers": [{k: v(t) for k, t in layer.leaves().items()}
                           for layer in net.layers]}

    def serving_cache_spec(self):
        """Keys and values a kv-head a layer, in TWO kinds of cache layer: the
        full layers' (every position) FIRST, whatever the first layer is, then
        the sliding layers', which keep a row's last ``sliding_window``
        positions."""
        from ..inference.serving_model import CacheKind, CacheSpec

        cfg = self.config
        KV, D = cfg.num_key_value_heads, cfg.head_dim
        kinds = (CacheKind("global", len(cfg.layers_of(False))),
                 CacheKind("window", len(cfg.layers_of(True)), cfg.sliding_window))
        kinds = tuple(k for k in kinds if k.layers)
        return CacheSpec(
            arrays=(("k", lambda bs: (KV, bs, D)), ("v", lambda bs: (KV, bs, D))),
            layers=cfg.num_hidden_layers,
            key=("cohere2_moe", cfg.hidden_size, cfg.num_attention_heads, KV, D,
                 tuple(cfg.layer_types), cfg.sliding_window, cfg.intermediate_size,
                 cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts,
                 cfg.experts_held, float(cfg.layer_norm_eps), float(cfg.logit_scale)),
            kv_heads=KV, head_dim=D, quantizable=False, transferable=False, kinds=kinds,
            why_not=("its sliding layers GIVE BACK the blocks behind their last "
                     f"{cfg.sliding_window} positions while a row runs, so a request's "
                     "blocks are not all its positions: a published or exported prefix has "
                     "lost its sliding layers' blocks, a refused draft may lie past a block "
                     "already given back, and the int8 scales follow ONE pool's blocks "
                     "(ROADMAP A3)"))

    def serving_rope(self, max_seq_len):
        # blha's layout [2, Br=1, Smax, 1, D/2]
        return rope_table(self.config, max_seq_len)[:, None, :, None, :]

    def serving_trunk(self, *, block_size, cache_quant="none"):
        """trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
        scales) -> (hidden [T, E] after the final norm and ``logit_scale``,
        caches, [], counts): packed tokens through every layer; ``caches`` =
        (key pools, value pools), the full layers' first, then the sliding
        layers'; ``bt`` a table a kind in that order.  A layer's five products
        (q, k, v, the router, the shared gate and up) read the ONE normed
        ``u``; attention, shared and routed experts meet in ONE add.
        ``counts``: the expert layers' seven (``held_experts``; every one of
        ``moe_tokens`` passes the shared experts too), ONE full layer's six
        (``paged_counts``), and by kind ONE layer's
        ``attn_positions_live.<kind>`` / ``attn_positions_read.<kind>`` /
        ``attn_chunks_kernel.<kind>`` with ``window_positions_spared``, as
        models/smallthinker.py's."""
        from ..ops.paged_attention import blha_attention, first_key, paged_counts

        cfg = self.config
        H, KV, D, eps = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                         cfg.layer_norm_eps)
        order = cfg.layers_of(False) + cfg.layers_of(True)
        cache_of = {l: i for i, l in enumerate(order)}
        two = bool(cfg.layers_of(True))
        W = cfg.sliding_window

        def trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
                  scales=None):
            key_caches, value_caches = caches
            tables = bt if two else (bt, bt)
            T, B = token_ids.shape[0], tables[0].shape[0]
            _, _, valid = token_coords(T, dec, now, cu, B)
            live = jnp.sum(valid).astype(jnp.int32)
            with jax.named_scope("embed"):
                hidden = weights["embed"][token_ids]
            counts = {name: jnp.zeros((), jnp.int32) for name in (
                "moe_tokens", "moe_local_picks", "experts_touched",
                "expert_tiles", "expert_tile_rows", "expert_tile_rows_live",
                "expert_rows_grouped")}
            for li, lw in enumerate(weights["layers"]):
                windowed = cfg.windowed(li)
                with jax.named_scope("norm"):
                    u = layer_norm(hidden, lw["ln"], None, eps)
                with jax.named_scope("attn_proj"):
                    qkv = jnp.concatenate([u @ lw["wq"], u @ lw["wk"], u @ lw["wv"]], axis=-1)
                ci = cache_of[li]
                with jax.named_scope("attention"):
                    out, key_caches[ci], value_caches[ci], *_ = blha_attention(
                        qkv, key_caches[ci], value_caches[ci], enc, dec, now, cu,
                        tables[windowed], num_heads=H, kv_num_heads=KV, head_dim=D,
                        block_size=block_size, max_q_len=mq, use_neox_style=False,
                        compute_dtype=hidden.dtype,
                        rope_emb=rope if windowed else None,
                        window=W if windowed else None)
                with jax.named_scope("attn_out"):
                    attn = out @ lw["wo"]
                hidden = _join(hidden, attn, _shared(cfg, lw, u),
                               _routed(cfg, lw, u, valid, counts))
                counts["moe_tokens"] += live        # they all pass the shared experts too
            with jax.named_scope("norm"):
                hidden = layer_norm(hidden, weights["norm"], None, eps)
                if cfg.logit_scale != 1:        # linear: the head's scale, on its rows
                    hidden = hidden * jnp.asarray(cfg.logit_scale, hidden.dtype)
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
