"""Ouro (``model_type`` ``ouro``): a LOOPED language model.  One stack of
decoder layers is run ``total_ut_steps`` times over the same weights, the
model's final norm after every pass, and an exit gate reads each pass's
result.  Published config, whose key names ``OuroConfig`` keeps:
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json

For ``x_0 = Emb(ids)`` and passes ``r = 0 .. R-1`` (``n`` an RMSNorm with a
learned gain, no bias anywhere):
    layer l:  a = x + n2_l(Attn_l(n1_l(x)));  y = a + n4_l(MLP_l(n3_l(a)))
    Attn_l:   [q | k | v] = h [Wq | Wk | Wv]; rotate-half RoPE on q and k at the
              token's position (the SAME position in every pass); causal
              softmax attention, scale head_dim^-0.5, over the keys and
              values THIS layer wrote in THIS pass; then Wo
    MLP_l:    (silu(h Wg) * (h Wu)) Wd
    pass r:   x_{r+1} = norm(L_{depth-1}(... L_0(x_r)))
    gate:     lambda_r = sigmoid(w_g . x_{r+1} + b_g); exit distribution
              p(r) = lambda_r prod_{j<r}(1 - lambda_j), the last pass taking
              what is left; a token leaves at the first pass whose cumulative
              p reaches ``early_exit_threshold``
    logits:   x_R W_head at the published threshold 1: every token runs every
              pass.  A threshold under 1 is another deployment (rows that
              leave the loop at different passes: ROADMAP) and is refused.

The layers' weights are kept STACKED, one parameter ``[depth, ...]`` a leaf:
both forms below run the layers as a loop in the compiled program, so the
program does not grow with the depth or with the number of passes.  The
three attention projections are ONE leaf ``[depth, hidden, q + k + v]``:
held apart and joined after the products, the TPU compiler wanted each of
them transposed and copied all three at the head of every program (1.21 GB
of temporaries at the published size; compile, PR 30).

Two forms of the same mathematics: ``forward`` (whole sequences, every pass
attending its own full keys and values) and ``serving_trunk`` (packed tokens
against the engine's paged cache, ops/paged_attention.py: pass r of layer l
keeps its own keys and values in cache layer ``r * depth + l`` of ONE pool
with a leading layer axis, ``total_ut_steps x depth`` cache layers for
``depth`` layers of weights)."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import Constant, Normal
from ..ops.dispatch import apply
from ..ops.paged_attention import blha_attention, paged_counts, rope_rotate
from ..profiler import SetupSpan
from .pangu_moe import _rms, _swiglu          # the sandwich block's two, as openPangu has them

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "exit_distribution"]

F32 = jnp.float32


@dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    dtype: str = "float32"

    def __post_init__(self):
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps={self.total_ut_steps}: at least one pass")
        if float(self.early_exit_threshold) != 1.0:
            raise ValueError(
                f"early_exit_threshold={self.early_exit_threshold} is not served: "
                "under 1 a token leaves the loop at the first pass whose "
                "cumulative exit probability reaches it, so the rows of one "
                "batch run different numbers of passes and stop writing the "
                "later passes' cache layers, which neither forward nor the "
                "serving trunk does (ROADMAP queue A). At the published 1 "
                "every token runs all total_ut_steps passes")
        if (self.hidden_act != "silu" or self.tie_word_embeddings
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("ouro as published: SwiGLU, an untied head, query "
                             "heads a multiple of the kv heads")


# ------------------------------------------------------------ the mathematics
# Pure functions of arrays, shared by ``forward`` and the trunk.
def _mlp(lw, h):
    return _swiglu(h, lw["wg"], lw["wu"], lw["wd"])


def _gate(p, x):
    """lambda [...] float32: the exit gate on a pass's normed result."""
    z = jnp.einsum("...e,e->...", x.astype(F32), p["gate_w"].astype(F32),
                   precision=jax.lax.Precision.HIGHEST)
    return jax.nn.sigmoid(z + p["gate_b"].astype(F32))


def exit_distribution(lam):
    """lambda [R, ...] -> p [R, ...]: p(r) = lambda_r prod_{j<r}(1 - lambda_j),
    the last pass taking what is left."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def rope_table(cfg, length):
    """[2, length, D/2] float32 (cos, sin): rotate-half, no scaling."""
    d = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    fr = np.outer(np.arange(length, dtype=np.float64), inv)
    return jnp.asarray(np.stack([np.cos(fr), np.sin(fr)]), F32)


def _attn_full(cfg, lw, h, cos, sin):
    """h [B, S, E], causal over each sequence; cos/sin [S, D/2]."""
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    B, S, _ = h.shape
    qkv = h @ lw["wqkv"]
    q = rope_rotate(qkv[..., :H * D].reshape(B, S, H, D), cos[:, None], sin[:, None], True)
    k = rope_rotate(qkv[..., H * D:(H + KV) * D].reshape(B, S, KV, D),
                    cos[:, None], sin[:, None], True)
    v = qkv[..., (H + KV) * D:].reshape(B, S, KV, D)
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=F32) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1).astype(h.dtype), v)
    return o.reshape(B, S, H * D) @ lw["wo"]


def _passes_full(cfg, p, ids):
    """ids [B, S] -> x_1 .. x_R [R, B, S, E]: every pass's normed result."""
    eps = cfg.rms_norm_eps
    rope = rope_table(cfg, ids.shape[1])

    def layer(x, lw):
        a = x + _rms(_attn_full(cfg, lw, _rms(x, lw["ln1"], eps), rope[0], rope[1]),
                     lw["ln2"], eps)
        return a + _rms(_mlp(lw, _rms(a, lw["ln3"], eps)), lw["ln4"], eps), None

    def one_pass(x, _):
        x = _rms(jax.lax.scan(layer, x, p["layers"])[0], p["norm"], eps)
        return x, x

    return jax.lax.scan(one_pass, p["embed"][ids], None, length=cfg.total_ut_steps)[1]


# ------------------------------------------------------------------ the layers
def _matrix(layer, shape, dtype):
    return layer.create_parameter(list(shape), dtype=dtype,
                                  default_initializer=Normal(0.0, shape[-2] ** -0.5))


def _gain(layer, shape, dtype):
    return layer.create_parameter(list(shape), dtype=dtype,
                                  default_initializer=Constant(1.0))


class OuroLayerStack(nn.Layer):
    """The decoder layers, every leaf stacked on a leading depth axis.  The
    attribute names are the published modules' (``input_layernorm_2`` and
    ``post_attention_layernorm_2`` are the sandwich's two further norms)."""

    def __init__(self, cfg: OuroConfig):
        super().__init__()
        L, e, f, dt = (cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size,
                       cfg.dtype)
        h, kv = (n * cfg.head_dim for n in (cfg.num_attention_heads,
                                            cfg.num_key_value_heads))
        self.input_layernorm = _gain(self, (L, e), dt)
        self.input_layernorm_2 = _gain(self, (L, e), dt)
        self.post_attention_layernorm = _gain(self, (L, e), dt)
        self.post_attention_layernorm_2 = _gain(self, (L, e), dt)
        # the published q_proj | k_proj | v_proj side by side, [in, q + k + v]:
        # one matmul, and one layout (module docstring)
        self.qkv_proj = _matrix(self, (L, e, h + 2 * kv), dt)
        self.o_proj = _matrix(self, (L, h, e), dt)
        self.gate_proj = _matrix(self, (L, e, f), dt)
        self.up_proj = _matrix(self, (L, e, f), dt)
        self.down_proj = _matrix(self, (L, f, e), dt)

    def leaves(self):
        return {"ln1": self.input_layernorm, "ln2": self.input_layernorm_2,
                "ln3": self.post_attention_layernorm,
                "ln4": self.post_attention_layernorm_2,
                "wqkv": self.qkv_proj, "wo": self.o_proj, "wg": self.gate_proj,
                "wu": self.up_proj, "wd": self.down_proj}


class OuroModel(nn.Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Layer()
        self.embed_tokens.weight = self.embed_tokens.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(0.0, 1.0))
        self.layers = OuroLayerStack(cfg)
        self.norm = nn.Layer()
        self.norm.weight = _gain(self.norm, (cfg.hidden_size,), cfg.dtype)
        self.early_exit_gate = nn.Layer()
        self.early_exit_gate.weight = self.early_exit_gate.create_parameter(
            [cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(0.0, cfg.hidden_size ** -0.5))
        self.early_exit_gate.bias = self.early_exit_gate.create_parameter(
            [1], dtype=cfg.dtype, default_initializer=Constant(0.0))


class OuroForCausalLM(nn.Layer):
    def __init__(self, cfg: OuroConfig):
        with SetupSpan("model.init", family=type(self).__name__, dtype=cfg.dtype) as span:
            super().__init__()
            self.config = cfg
            self.model = OuroModel(cfg)
            self.lm_head = nn.Layer()
            self.lm_head.weight = _matrix(self.lm_head, (cfg.hidden_size, cfg.vocab_size),
                                          cfg.dtype)
            span.note(parameters=self.num_params())

    def leaves(self):
        """Every parameter, in the structure the pure functions read."""
        m = self.model
        return {"embed": m.embed_tokens.weight, "norm": m.norm.weight,
                "head": self.lm_head.weight, "gate_w": m.early_exit_gate.weight,
                "gate_b": m.early_exit_gate.bias, "layers": m.layers.leaves()}

    def forward(self, input_ids, all_passes: bool = False):
        """[B, S] ids -> logits [B, S, V] of the last pass.  ``all_passes``:
        -> (logits [R, B, S, V] of every pass, lambda [R, B, S] float32, the
        exit gate's probability after each)."""
        cfg = self.config
        tree = self.leaves()
        flat, treedef = jax.tree_util.tree_flatten(
            tree, is_leaf=lambda t: hasattr(t, "_value"))

        def ouro_forward(*vals):
            p = jax.tree_util.tree_unflatten(treedef, vals[:-1])
            xs = _passes_full(cfg, p, vals[-1])
            if not all_passes:
                return xs[-1] @ p["head"]
            return xs @ p["head"], _gate(p, xs)

        return apply(ouro_forward, *flat, input_ids, op_name="ouro_forward",
                     n_outs=2 if all_passes else 1)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ---------------------------------------------- what a serving engine asks
    # (inference/serving_model.py: weights, cache specification, trunk, rope)
    def serving_weights(self, dtype):
        """The trunk's weight pytree: ``layers`` one dict of stacked leaves,
        the parameters' own arrays where ``dtype`` is theirs (no second copy
        of the model beside the pool)."""
        return jax.tree_util.tree_map(lambda t: t._value.astype(dtype), self.leaves(),
                                      is_leaf=lambda t: hasattr(t, "_value"))

    def serving_cache_spec(self):
        """Keys and values a kv-head, a cache layer a (pass, layer): ONE
        ``[passes x depth, nb, KV, bs, D]`` array each."""
        from ..inference.serving_model import CacheSpec

        cfg = self.config
        KV, D = cfg.num_key_value_heads, cfg.head_dim

        def block(bs):
            return (KV, bs, D)

        return CacheSpec(
            arrays=(("k", block), ("v", block)),
            layers=cfg.total_ut_steps * cfg.num_hidden_layers,
            key=("ouro", cfg.num_attention_heads, KV, D, cfg.hidden_size,
                 cfg.num_hidden_layers, cfg.total_ut_steps, float(cfg.rms_norm_eps)),
            kv_heads=KV, head_dim=D, stacked=True, passes=cfg.total_ut_steps,
            quantizable=False,
            why_not=("the layers are a loop in the compiled program over one "
                     "pool with a layer axis, and the int8 cache's scales are "
                     "a Python list a layer that only an unrolled trunk can "
                     "index (ROADMAP queue A)"))

    def serving_rope(self, max_seq_len):
        # blha's layout [2, Br=1, Smax, 1, D/2]
        return rope_table(self.config, max_seq_len)[:, None, :, None, :]

    def serving_trunk(self, *, block_size, cache_quant="none"):
        """trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
        scales) -> (hidden [T, E] of the last pass, normed, caches, [],
        counts): packed tokens through ``total_ut_steps`` passes of the layers
        against the paged cache.  Both loops, over the passes and over the
        layers, are loops of the compiled program; the cache layer of (pass
        r, layer l) is ``r * depth + l``, written and read in place.
        ``counts``: ``loop_tokens`` (tokens fed), ``loop_token_passes``
        (tokens x the passes each ran: a token runs a pass while its
        cumulative exit probability is under the threshold, at 1 all of
        them), and ``paged_counts``'s six of ONE cache layer (all alike)."""
        cfg = self.config
        H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        L, R, eps, bs = (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.rms_norm_eps,
                         block_size)
        threshold = float(cfg.early_exit_threshold)

        def trunk(weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
                  scales=None):
            T, B = token_ids.shape[0], bt.shape[0]
            tok = jnp.arange(T, dtype=jnp.int32)
            b_idx = jnp.clip(
                jnp.searchsorted(cu, tok, side="right").astype(jnp.int32) - 1, 0, B - 1)
            valid = (tok < cu[-1]) & (tok - cu[b_idx] < now[b_idx])

            def layer(carry, lw):
                hidden, kc, vc, at = carry
                with jax.named_scope("norm"):
                    h = _rms(hidden, lw["ln1"], eps)
                with jax.named_scope("attn_proj"):
                    qkv = h @ lw["wqkv"]
                out, kc, vc = blha_attention(
                    qkv, kc, vc, enc, dec, now, cu, bt, num_heads=H, kv_num_heads=KV,
                    head_dim=D, block_size=bs, max_q_len=mq, use_neox_style=True,
                    compute_dtype=hidden.dtype, rope_emb=rope, layer=at)[:3]
                with jax.named_scope("attn_out"):
                    attn = out @ lw["wo"]
                with jax.named_scope("post_norm"):
                    hidden = hidden + _rms(attn, lw["ln2"], eps)
                with jax.named_scope("norm"):
                    h2 = _rms(hidden, lw["ln3"], eps)
                with jax.named_scope("mlp"):
                    ffn = _mlp(lw, h2)
                with jax.named_scope("post_norm"):
                    hidden = hidden + _rms(ffn, lw["ln4"], eps)
                return (hidden, kc, vc, at + 1), None

            @jax.named_scope("loop_pass")
            def one_pass(r, carry):
                hidden, kc, vc, stay, ran = carry
                # a token runs this pass while it has not left before it
                ran = ran + jnp.sum(valid & (1.0 - stay < threshold)).astype(jnp.int32)
                (hidden, kc, vc, _), _ = jax.lax.scan(
                    layer, (hidden, kc, vc, r * L), weights["layers"])
                with jax.named_scope("norm"):
                    hidden = _rms(hidden, weights["norm"], eps)
                with jax.named_scope("exit_gate"):
                    stay = stay * (1.0 - _gate(weights, hidden))
                return hidden, kc, vc, stay, ran

            with jax.named_scope("embed"):
                hidden = weights["embed"][token_ids]
            hidden, kc, vc, _, ran = jax.lax.fori_loop(
                0, R, one_pass,
                (hidden,) + tuple(caches) + (jnp.ones((T,), F32), jnp.zeros((), jnp.int32)))
            paged = paged_counts(hidden.dtype, kc, dec, now, cu, bt, tokens=T, heads=H,
                                 max_q_len=mq)
            return hidden, (kc, vc), [], {
                "loop_tokens": jnp.sum(valid).astype(jnp.int32),
                "loop_token_passes": ran, **paged}

        return trunk
