"""DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``): multi-head latent
attention whose visible set is a LEARNED selection (DeepSeek Sparse
Attention: a lightning indexer scores every context position for a query and
the attention sees the ``index_topk`` best), two RMSNorms a layer, leading
dense SwiGLU layers, then sigmoid-routed experts chosen under a GROUP limit
with a selection bias (``noaux_tc``) beside one shared expert, YaRN rope, a
next-token (MTP) module.  Published config:
https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json
whose key names ``DeepseekV32Config`` keeps.

Layer (``n`` an RMSNorm with a gain):  h = x + MLA(n_in(x));  y = h + FFN(n_post(h))
MLA: as models/pangu_moe.py (``_latent_proj``: c_q = n_q(x W_qa), [q_nope |
    q_rope] = c_q W_qb a head, [c | k_r] = x W_kva, c = n_kv(c), rope on q_rope
    and k_r, [k_nope | v] = c W_kvb a head), the softmax scale (nope +
    rope)**-0.5 * mscale**2, over the selection S_t only.
YaRN: inv_i = theta**(-2i/R); ramp_i = clip((i - lo) / (hi - lo), 0, 1) with
    (lo, hi) the correction range of beta_fast and beta_slow over the original
    context; inv'_i = inv_i / factor * ramp_i + inv_i * (1 - ramp_i);
    mscale = 0.1 * mscale_all_dim * ln(factor) + 1.
Indexer (a layer's own W_iq, W_ik, W_iw and a LayerNorm; ops/sparse_index.py):
    q^I_j = (c_q W_iq)_j for J heads, k^I = LayerNorm(x W_ik), rope on the
    first R dimensions of both; w = (x W_iw) * J**-0.5 * D**-0.5 in float32;
    I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s]);  S_t = the min(index_topk,
    t + 1) positions s <= t of highest I, a tie to the lower position.
Experts: g = sigmoid(float32(x) float32(W_r)); g' = g + b (b the selection
    bias, a weight); ``n_group`` groups, a group's score the sum of its two
    highest g'; the ``topk_group`` best groups stay; I = top-k of g' inside
    them; w_i = scale * g_i / sum_{j in I} g_j (from g, WITHOUT the bias);
    FFN(x) = SwiGLU_shared(x) + sum_{i in I, i held} w_i SwiGLU_i(x).

Departures from the published inference code: its indexer rotates q^I and k^I
by a Hadamard matrix and quantises them to FP8; the rotation is orthogonal
(every dot stays what it was) and the chip has no FP8 unit, so both are left
out and ``index_k`` is kept in the model's type; rope pairs by halves here
(``rope_half``), in MLA and in the indexer.

Everything the two models share has ONE definition, in models/pangu_moe.py
(``_latent_proj``, ``_moe_ffn``, the layers' plumbing) and
ops/latent_attention.py; ``experts_held`` means here what it means there.
Two forms of the same mathematics: ``forward`` (whole sequences, keys and
values expanded a head, the selection as a mask over the causal square) and
``serving_trunk`` (packed tokens against the engine's paged pool of TWO
arrays a layer, ``latent`` and ``index_k``, under one block table)."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import Constant
from ..ops.latent_attention import (latent_attention, latent_counts, rope_half,
                                    selection_counts, token_coords)
from ..ops.sparse_index import index_scores, layer_norm, select_topk, sparse_index
from .pangu_moe import (F32, HIGHEST, LatentMoEGeometry, PanguDecoderLayer, PanguMLAttention,
                        PanguMLP, PanguMTPModule, PanguSparseMoE, PanguUltraMoEForCausalLM,
                        PanguUltraMoEModel, _apply, _Dense, _Gain, _latent_proj, _moe_ffn,
                        _q_latent, _rms, _swiglu)

__all__ = ["DeepseekV32Config", "DeepseekV32Model", "DeepseekV32ForCausalLM",
           "deepseek_v32_tiny", "route_grouped"]

_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
         "original_max_position_embeddings": 4096, "type": "yarn"}


@dataclass
class DeepseekV32Config(LatentMoEGeometry):
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = field(default_factory=lambda: dict(_YARN))
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    ep_size: int = 1
    dtype: str = "float32"
    # the routed experts this chip holds, [lo, hi) of n_routed_experts; None: all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        self._check_experts_held()
        if (self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc"
                or not self.norm_topk_prob or self.attention_bias
                or self.hidden_act != "silu" or self.n_shared_experts != 1
                or self.tie_word_embeddings or self.moe_layer_freq != 1):
            raise ValueError("deepseek_v32 as published: sigmoid scores chosen by "
                             "noaux_tc, top-k weights normalised, one shared expert, "
                             "an expert layer after every dense one, SwiGLU, no "
                             "biases, untied head")
        if self.n_routed_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError(f"{self.n_routed_experts} routed experts do not fall into "
                             f"n_group={self.n_group} groups of which topk_group="
                             f"{self.topk_group} stay")
        if self.rope_scaling is not None and self.rope_scaling.get("type") != "yarn":
            raise ValueError("rope_scaling is YaRN's or None")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("the indexer ropes the first qk_rope_head_dim of its "
                             "index_head_dim dimensions")

    @property
    def mscale(self) -> float:
        """YaRN's attention factor over all dimensions (1 without scaling)."""
        rs = self.rope_scaling or {}
        if rs.get("factor", 1) <= 1 or not rs.get("mscale_all_dim", 0):
            return 1.0
        return 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5 * self.mscale ** 2


def deepseek_v32_tiny(**kw) -> DeepseekV32Config:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
                num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                index_n_heads=4, index_head_dim=16, index_topk=8, n_routed_experts=16,
                num_experts_per_tok=4, n_group=4, topk_group=2,
                num_nextn_predict_layers=0, max_position_embeddings=256,
                rope_scaling=dict(_YARN, factor=4, original_max_position_embeddings=64))
    base.update(kw)
    return DeepseekV32Config(**base)


# ------------------------------------------------------------ the mathematics
def rope_table(cfg, length):
    """[2, length, R/2] float32 (cos, sin): rotate-half, YaRN's frequencies."""
    r = cfg.qk_rope_head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    rs = cfg.rope_scaling
    mscale = 1.0
    if rs is not None:
        orig = rs["original_max_position_embeddings"]

        def turns(beta):      # the dimension that makes ``beta`` turns over ``orig``
            return r * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

        lo = max(math.floor(turns(rs["beta_fast"])), 0)
        hi = min(math.ceil(turns(rs["beta_slow"])), r // 2 - 1)
        ramp = np.clip((np.arange(r // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
        inv = inv / rs["factor"] * ramp + inv * (1.0 - ramp)

        def att(m):
            return 0.1 * m * math.log(rs["factor"]) + 1.0 if rs["factor"] > 1 and m else 1.0

        mscale = att(rs.get("mscale", 1)) / att(rs.get("mscale_all_dim", 0))
    fr = np.outer(np.arange(length, dtype=np.float64), inv)
    return jnp.asarray(np.stack([np.cos(fr), np.sin(fr)]) * mscale, F32)


def _rope_first(x, r, cos, sin):
    """Rope on the first ``r`` of the last axis' dimensions."""
    return jnp.concatenate([rope_half(x[..., :r], cos, sin), x[..., r:]], axis=-1)


def _index_proj(cfg, p, x, c_q, cos, sin):
    """x [T, E], c_q [T, q_lora_rank] -> the indexer's queries [T, J, D], its
    head weights [T, J] float32 and its keys [T, D]."""
    J, D, R = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _rope_first((c_q @ p["wiq"]).reshape(-1, J, D), R, cos, sin)
    k = _rope_first(layer_norm(x @ p["wik"], p["ik_norm_w"], p["ik_norm_b"], 1e-6),
                    R, cos, sin)
    w = jnp.dot(x, p["wiw"], preferred_element_type=F32) * (J ** -0.5 * D ** -0.5)
    return q, w, k


def _mla_dsa_full(cfg, p, x):
    """One sequence [S, E]: the indexer's selection as a mask over the causal
    square, keys and values expanded a head."""
    H, N, V = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    S = x.shape[0]
    rope = rope_table(cfg, S)
    c_q = _q_latent(cfg, p, x)
    q_n, q_r, c, k_r = _latent_proj(cfg, p, x, rope[0], rope[1], c_q)
    qi, wi, ki = _index_proj(cfg, p, x, c_q, rope[0], rope[1])
    selected = select_topk(index_scores(qi, wi, ki), jnp.arange(1, S + 1), cfg.index_topk)
    kv = (c @ p["wkv_b"]).reshape(S, H, N + V)
    s = (jnp.einsum("qhn,khn->hqk", q_n, kv[..., :N], preferred_element_type=F32)
         + jnp.einsum("qhr,kr->hqk", q_r, k_r, preferred_element_type=F32))
    s = jnp.where(selected[None], s * cfg.softmax_scale, -1e30)
    o = jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1).astype(x.dtype), kv[..., N:])
    return o.reshape(S, H * V) @ p["wo"]


def route_grouped(x, w_router, bias, top_k, scale, n_group, topk_group):
    """``noaux_tc``: sigmoid scores in float32 over every routed expert; the
    CHOICE is made on score + bias, inside the ``topk_group`` groups whose two
    best such sums are highest; the WEIGHTS are the chosen experts' scores
    without the bias, normalised over the ``top_k`` and scaled.
    -> (idx [T, k] int32, w [T, k] float32)."""
    with jax.named_scope("router"):
        g = jax.nn.sigmoid(jnp.dot(x.astype(F32), w_router.astype(F32), precision=HIGHEST))
        choice = (g + bias.astype(F32)).reshape(g.shape[0], n_group, -1)
        group_score = jnp.sum(jax.lax.top_k(choice, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=-2)      # [T, n_group]
        choice = jnp.where(kept[..., None], choice, -jnp.inf).reshape(g.shape)
        _, idx = jax.lax.top_k(choice, top_k)
        gv = jnp.take_along_axis(g, idx, axis=-1)
        return idx.astype(jnp.int32), scale * gv / jnp.sum(gv, -1, keepdims=True)


def _router_of(cfg, p):
    """The ``router=`` of ``pangu_moe._moe_ffn`` for a layer's weights."""
    def router(x, w_router, top_k, scale):
        return route_grouped(x, w_router, p["router_bias"], top_k, scale,
                             cfg.n_group, cfg.topk_group)
    return router


# ------------------------------------------------------------------ the layers
class DeepseekV32Attention(PanguMLAttention):
    """Latent attention's projections and the lightning indexer's."""

    def __init__(self, cfg: DeepseekV32Config):
        super().__init__(cfg)
        e, dt, d = cfg.hidden_size, cfg.dtype, cfg.index_head_dim
        self.indexer = nn.Layer()
        self.indexer.wq_b = _Dense(cfg.q_lora_rank, cfg.index_n_heads * d, dt)
        self.indexer.wk = _Dense(e, d, dt)
        self.indexer.k_norm = _Gain(d, dt)
        self.indexer.k_norm.bias = self.indexer.k_norm.create_parameter(
            [d], dtype=dt, default_initializer=Constant(0.0))
        self.indexer.weights_proj = _Dense(e, cfg.index_n_heads, dt)

    def leaves(self):
        ix = self.indexer
        return dict(super().leaves(), wiq=ix.wq_b.weight, wik=ix.wk.weight,
                    ik_norm_w=ix.k_norm.weight, ik_norm_b=ix.k_norm.bias,
                    wiw=ix.weights_proj.weight)

    def forward(self, hidden):
        """hidden [B, S, E] -> [B, S, E], each query over its own selection."""
        cfg = self.cfg

        def mla_dsa(p, x):
            return jax.vmap(lambda seq: _mla_dsa_full(cfg, p, seq))(x)

        return _apply(mla_dsa, self.leaves(), hidden)


class DeepseekV32MoE(PanguSparseMoE):
    """The expert layer with the selection bias (``e_score_correction_bias``)."""

    def __init__(self, cfg: DeepseekV32Config):
        super().__init__(cfg)
        self.gate.e_score_correction_bias = self.gate.create_parameter(
            [cfg.n_routed_experts], dtype="float32", default_initializer=Constant(0.0))

    def leaves(self):
        return dict(super().leaves(), router_bias=self.gate.e_score_correction_bias)

    def forward(self, x):
        cfg = self.cfg

        def moe_ffn(p, x):
            y, _ = _moe_ffn(cfg, p, x.reshape(-1, x.shape[-1]), router=_router_of(cfg, p))
            return y.reshape(x.shape)

        return _apply(moe_ffn, self.leaves(), x)


class DeepseekV32DecoderLayer(nn.Layer):
    def __init__(self, cfg: DeepseekV32Config, sparse: bool):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = _Gain(cfg.hidden_size, cfg.dtype)
        self.self_attn = DeepseekV32Attention(cfg)
        self.post_attention_layernorm = _Gain(cfg.hidden_size, cfg.dtype)
        self.mlp = DeepseekV32MoE(cfg) if sparse else PanguMLP(cfg, cfg.intermediate_size)

    def leaves(self):
        out = {"ln_in": self.input_layernorm.weight,
               "ln_post": self.post_attention_layernorm.weight}
        out.update(self.self_attn.leaves())
        out.update(self.mlp.leaves())
        return out

    _norm = PanguDecoderLayer._norm

    def forward(self, x):
        h = x + self.self_attn(self._norm(x, self.input_layernorm))
        return h + self.mlp(self._norm(h, self.post_attention_layernorm))


class DeepseekV32MTPModule(PanguMTPModule):
    layer_class = DeepseekV32DecoderLayer


class DeepseekV32Model(PanguUltraMoEModel):
    layer_class = DeepseekV32DecoderLayer


class DeepseekV32ForCausalLM(PanguUltraMoEForCausalLM):
    """``forward(ids, mtp=False)``, ``num_params`` and ``serving_weights`` are
    the base's (the next-token module merges [n_a(h_t) ; n_b(Emb(tok_{t+1}))]
    as the paper's equation 21 orders them)."""

    backbone_name = "model"                     # the published checkpoints' prefix
    model_class = DeepseekV32Model
    mtp_class = DeepseekV32MTPModule

    # ---------------------------------------------- what a serving engine asks
    def serving_weights(self, dtype):
        """The base's pytree; the selection bias stays float32, as published."""
        w = super().serving_weights(dtype)
        for lw, layer in zip(w["layers"], self.model.layers):
            if "router_bias" in lw:
                lw["router_bias"] = layer.mlp.gate.e_score_correction_bias._value
        return w

    def serving_cache_spec(self):
        from ..inference.serving_model import CacheSpec

        cfg = self.config
        return CacheSpec(
            arrays=(("latent", lambda bs: (bs, cfg.latent_cache_width)),
                    ("index_k", lambda bs: (bs, cfg.index_head_dim))),
            layers=cfg.num_hidden_layers,
            key=("deepseek_v32", cfg.hidden_size, cfg.num_attention_heads,
                 cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                 cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.index_n_heads,
                 cfg.index_head_dim, cfg.index_topk, cfg.n_routed_experts,
                 cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group, cfg.experts_held,
                 cfg.first_k_dense_replace, float(cfg.routed_scaling_factor),
                 float(cfg.rms_norm_eps), float(cfg.softmax_scale)),
            quantizable=False, transferable=False,
            why_not=("a cache layer holds TWO arrays a token, 'latent' [kv_lora_rank + "
                     "qk_rope_head_dim] and 'index_k' [index_head_dim], neither with "
                     "kv heads: the int8 scales and the block wire format are per "
                     "kv-head and carry one keys/values pair (ROADMAP D3)"))

    def serving_rope(self, max_seq_len):
        return rope_table(self.config, max_seq_len)

    def serving_trunk(self, *, block_size, cache_quant="none"):
        """trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
        scales) -> (hidden [T, E] after the final norm, caches, [], counts):
        packed tokens through every layer against the paged pool, ``caches`` =
        (latent pools, index_k pools), a layer each.  ``counts``: the expert
        layers' three (``_moe_ffn``), and of ONE layer's indexer and attention:
        ``dsa_queries``, ``dsa_positions_scored``, ``dsa_positions_selected``
        (``sparse_index``) and ``dsa_positions_read`` (``selection_counts``)
        over the live queries whose context exceeds ``index_topk``,
        ``attn_positions_live`` (the context of every row fed) and
        ``latent_counts``'s two."""
        cfg = self.config
        eps, C = cfg.rms_norm_eps, cfg.kv_lora_rank
        pad = cfg.latent_cache_width - cfg.latent_width

        def trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
                  scales=None):
            lat, ik = caches
            T, B = token_ids.shape[0], bt.shape[0]
            coords = token_coords(T, dec, now, cu, B)
            _, abs_pos, valid = coords
            pos = jnp.clip(abs_pos, 0, rope.shape[1] - 1)
            cos, sin = rope[0, pos], rope[1, pos]
            with jax.named_scope("embed"):
                hidden = weights["embed"][token_ids]
            counts = {name: jnp.zeros((), jnp.int32) for name in (
                "moe_tokens", "moe_local_picks", "expert_rows_grouped")}
            asked = dict(heads=cfg.num_attention_heads, rank=C)
            counts.update(latent_counts(hidden.dtype, lat[0], now, bt, selected=True, **asked))
            for li, lw in enumerate(weights["layers"]):
                with jax.named_scope("norm"):
                    h = _rms(hidden, lw["ln_in"], eps)
                with jax.named_scope("latent_proj"):
                    c_q = _q_latent(cfg, lw, h)
                    q_n, q_r, c, k_r = _latent_proj(cfg, lw, h, cos, sin, c_q)
                    q_lat = jnp.einsum("thn,hnc->thc", q_n, lw["wuk"])
                    q = jnp.pad(jnp.concatenate([q_lat, q_r], axis=-1),
                                ((0, 0), (0, 0), (0, pad)))
                    entries = jnp.pad(jnp.concatenate([c, k_r], axis=-1),
                                      ((0, 0), (0, pad)))
                with jax.named_scope("indexer"), jax.named_scope("index_proj"):
                    qi, wi, ki = _index_proj(cfg, lw, h, c_q, cos, sin)
                selection, ik[li], dsa = sparse_index(
                    qi, wi, ki, ik[li], dec, now, cu, bt, coords,
                    topk=cfg.index_topk, max_q_len=mq)
                o_lat, lat[li] = latent_attention(
                    q, entries, lat[li], dec, now, cu, bt, rank=C, max_q_len=mq,
                    scale=cfg.softmax_scale, selection=selection)
                if li == 0:
                    counts.update(dsa, **selection_counts(
                        hidden.dtype, lat[0], dec, now, bt, selection, topk=cfg.index_topk,
                        max_q_len=mq, **asked),
                        attn_positions_live=jnp.sum(
                            jnp.where(now > 0, dec + now, 0)).astype(jnp.int32))
                with jax.named_scope("attn_out"):
                    o = jnp.einsum("thc,hcv->thv", o_lat, lw["wuv"])
                    hidden = hidden + o.reshape(T, -1) @ lw["wo"]
                with jax.named_scope("norm"):
                    h2 = _rms(hidden, lw["ln_post"], eps)
                if "router" in lw:
                    ffn, _ = _moe_ffn(cfg, lw, h2, valid, router=_router_of(cfg, lw),
                                      counts=counts)
                else:
                    with jax.named_scope("mlp"):
                        ffn = _swiglu(h2, lw["wg"], lw["wu"], lw["wd"])
                hidden = hidden + ffn
            with jax.named_scope("norm"):
                hidden = _rms(hidden, weights["norm"], eps)
            return hidden, (lat, ik), [], counts

        return trunk
