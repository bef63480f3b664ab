"""Llama model family — the flagship decoder LM.

Capability target: PaddleNLP's Llama implementation exercised by BASELINE
(Llama-7B pretrain tokens/sec/chip); the reference framework supplies its
building blocks (fused rope/rms_norm/swiglu:
/root/reference/python/paddle/incubate/nn/functional/, flash attention:
python/paddle/nn/functional/flash_attention.py:198, TP layers:
fleet/layers/mpu/mp_layers.py).

TPU-first construction: bf16 params, Pallas flash attention, RMSNorm in fp32
accumulation, rotary embeddings precomputed once, Column/RowParallel layers
that lower to GSPMD shardings on the 'mp' axis, batch sharded on 'dp', and
optional sequence-parallel activation sharding on 'sep'.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.fleet.mp_layers import ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding
from ..nn import functional as F
from ..ops.dispatch import apply
from ..ops.pallas.fused_ops import rope_fused, swiglu_fused
from ..profiler import SetupSpan
from ..tensor import manipulation as M
from ..tensor.tensor import Tensor

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "LlamaPretrainingCriterion",
           "llama_tiny", "llama_7b", "llama_pipeline_descs"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    recompute: bool = False  # activation checkpointing per decoder layer
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama_tiny(**kw) -> "LlamaConfig":
    return LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=352,
                       num_hidden_layers=2, num_attention_heads=4,
                       max_position_embeddings=256, **kw)


def llama_7b(**kw) -> "LlamaConfig":
    return LlamaConfig(**kw)


def _rope_cache(config: LlamaConfig):
    dim = config.head_dim
    inv_freq = 1.0 / (config.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    t = np.arange(config.max_position_embeddings, dtype=np.float64)
    freqs = np.outer(t, inv_freq)  # [S, dim/2]
    return np.cos(freqs).astype(np.float32), np.sin(freqs).astype(np.float32)


def apply_rotary_pos_emb(q, k, cos, sin, position_offset=0):
    """q/k: [B, S, H, D]; cos/sin buffers [Smax, D/2] (reference fused analog:
    incubate fused_rotary_position_embedding). ``position_offset`` may be a
    scalar Tensor (traced — the static-cache decode path slices the rope
    window with lax.dynamic_slice).

    An integer offset turns q and k by the table's rows ``[offset, offset +
    S)`` through ``rope_fused``: one op with its own backward (the same
    rotation with ``-sin``), which ``ops/pallas/fused_ops.py`` runs as its
    kernel or as its reference form by what the call shows. On
    mistral7b.train.pretrain-2k the kernels (this one and the SwiGLU's) read
    32,786 tokens/s against the 30,387 of the jnp chains they replaced (PR 50)."""
    if isinstance(position_offset, Tensor):
        def f_dyn(qv, kv, c, s, off):
            S = qv.shape[1]
            off = off.astype(jnp.int32)
            cw = jax.lax.dynamic_slice_in_dim(c, off, S)
            sw = jax.lax.dynamic_slice_in_dim(s, off, S)

            def rot(x):
                x1, x2 = jnp.split(x, 2, axis=-1)
                cb = cw[None, :, None, :]
                sb = sw[None, :, None, :]
                return jnp.concatenate([x1 * cb - x2 * sb, x2 * cb + x1 * sb],
                                       axis=-1).astype(x.dtype)

            return rot(qv), rot(kv)

        return apply(lambda *a: tuple(f_dyn(*a)), q, k, cos, sin, position_offset,
                     op_name="fused_rope_dyn", n_outs=2)

    def f(qv, kv, c, s):
        S = qv.shape[1]
        return tuple(rope_fused(qv, kv, c[position_offset : position_offset + S],
                                s[position_offset : position_offset + S]))

    return apply(f, q, k, cos, sin, op_name="fused_rope", n_outs=2)


def _hcg():
    from ..distributed.topology import get_hybrid_communicate_group

    return get_hybrid_communicate_group()


def _mp_active():
    hcg = _hcg()
    return hcg is not None and hcg.axis_size("mp") > 1


class LlamaAttention(nn.Layer):
    @staticmethod
    def _sep_mesh():
        hcg = _hcg()
        if hcg is not None and hcg.axis_size("sep") > 1:
            return hcg.mesh
        return None

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.head_dim = config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.q_proj = ColumnParallelLinear(h, self.num_heads * self.head_dim, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, self.num_kv_heads * self.head_dim, has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, self.num_kv_heads * self.head_dim, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(self.num_heads * self.head_dim, h, has_bias=False, input_is_parallel=True)

    def forward(self, hidden, cos, sin, attn_mask=None, cache=None):
        b, s = hidden.shape[0], hidden.shape[1]
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("attn_proj"):
            if s == 1 and cache is not None and not _mp_active():
                # decode step: ONE fused qkv matmul — the weight concat is loop-
                # invariant, so XLA hoists it out of the decode scan and the step
                # streams one [h, (nh+2·nkv)·hd] weight (measured 621→773 GB/s
                # vs three separate matmuls at decode shapes)
                def qkv_fused(hv, wq, wk, wv):
                    w = jnp.concatenate([wq, wk, wv], axis=1)
                    return hv @ w.astype(hv.dtype)

                qkv = apply(qkv_fused, hidden, self.q_proj.weight, self.k_proj.weight,
                            self.v_proj.weight, op_name="qkv_fused")
                qd, kd = nh * hd, nkv * hd
                q = M.reshape(qkv[:, :, :qd], [b, s, nh, hd])
                k = M.reshape(qkv[:, :, qd:qd + kd], [b, s, nkv, hd])
                v = M.reshape(qkv[:, :, qd + kd:], [b, s, nkv, hd])
            else:
                # a longer step: three products (joined they read 32,182
                # against 32,455 tokens/s on mistral7b.train.pretrain-2k: PR 50)
                q = M.reshape(self.q_proj(hidden), [b, s, nh, hd])
                k = M.reshape(self.k_proj(hidden), [b, s, nkv, hd])
                v = M.reshape(self.v_proj(hidden), [b, s, nkv, hd])
        if cache is not None and len(cache) == 3:
            with jax.named_scope("attention"):
                return self._static_cache_attn(q, k, v, cos, sin, cache, b, s)
        with jax.named_scope("attention"):
            offset = 0
            if cache is not None:
                offset = cache[0].shape[1]
            q, k = apply_rotary_pos_emb(q, k, cos, sin, position_offset=offset)
            new_cache = None
            if cache is not None:
                k = M.concat([cache[0], k], axis=1)
                v = M.concat([cache[1], v], axis=1)
                new_cache = (k, v)
            ring_mesh = self._sep_mesh() if (cache is None and attn_mask is None) else None
            if ring_mesh is not None:
                # sequence parallelism: exact blockwise ring attention over 'sep'
                from ..ops.ring_attention import ring_attention

                hcg = _hcg()
                b_ax = "dp" if hcg.axis_size("dp") > 1 else None
                mp_deg = hcg.axis_size("mp")
                h_ax = "mp" if mp_deg > 1 else None
                rep = self.num_heads // self.num_kv_heads

                def ring_fn(qv, kv, vv):
                    # GQA KV heads are indexed inside the ring/flash kernels;
                    # only when the KV head count cannot be sharded on mp do we
                    # fall back to repeating them up front
                    if rep > 1 and h_ax is not None and self.num_kv_heads % mp_deg:
                        kv = jnp.repeat(kv, rep, axis=2)
                        vv = jnp.repeat(vv, rep, axis=2)
                    return ring_attention(qv, kv, vv, mesh=ring_mesh, axis_name="sep",
                                          causal=True, batch_axis=b_ax, head_axis=h_ax)

                out = apply(ring_fn, q, k, v, op_name="ring_attention")
            elif attn_mask is None and cache is None:
                with jax.named_scope("flash_attention"):
                    out, _ = F.flash_attention(q, k, v, causal=True)
            else:
                out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                     is_causal=attn_mask is None)
        out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
        with jax.named_scope("attn_out"):
            out = self.o_proj(out)
        if cache is not None:
            return out, new_cache
        return out

    def _static_cache_attn(self, q, k, v, cos, sin, cache, b, s):
        """Fixed-size KV ring (serving decode): cache = (k_buf [B,L,KVH,D],
        v_buf, pos ()) — every decode step has identical shapes, so the whole
        loop runs from ONE compiled program (the reference's
        masked_multihead_attention decode analog). One token takes the
        native-layout einsum below, a longer step the masked sdpa path."""
        kbuf, vbuf, pos = cache
        q, k = apply_rotary_pos_emb(q, k, cos, sin, position_offset=pos)
        if s == 1 and self.num_heads % kbuf.shape[2] == 0:
            # native-layout decode attention: NO head-major transposes of
            # the ring (the sdpa path's swapaxes cost a full extra KV
            # pass); fp32 softmax; GQA via grouped reshape, K/V never
            # repeated. Ring writes stay XLA dynamic_update_slice — in a
            # scan carry they are in-place (measured free). A Pallas kernel
            # for this step read 299-366 GB/s against this path's 610-688
            # (per-head M=1 MXU dots don't pipeline; r4) and was removed.
            scale = 1.0 / math.sqrt(self.head_dim)

            def fused(qv, kv_, vv, kb, vb, p):
                p32 = p.astype(jnp.int32)
                kb = jax.lax.dynamic_update_slice(
                    kb, kv_.astype(kb.dtype), (0, p32, 0, 0))
                vb = jax.lax.dynamic_update_slice(
                    vb, vv.astype(vb.dtype), (0, p32, 0, 0))
                bq, _, nh, hd = qv.shape
                kvh = kb.shape[2]
                rep = nh // kvh
                L = kb.shape[1]
                qg = qv.reshape(bq, 1, kvh, rep, hd)
                sc = jnp.einsum("bqgrd,blgd->bgrql", qg, kb).astype(jnp.float32) * scale
                cols = jnp.arange(L)
                sc = jnp.where(cols[None, None, None, None, :] <= p32, sc, -1e30)
                pr = jax.nn.softmax(sc, axis=-1).astype(qv.dtype)
                o = jnp.einsum("bgrql,blgd->bqgrd", pr, vb)
                return o.reshape(bq, 1, nh, hd), kb, vb

            out, kbuf, vbuf = apply(fused, q, k, v, kbuf, vbuf, pos,
                                    op_name="decode_attention", n_outs=3)
            out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(out), (kbuf, vbuf, pos + s)

        def write(buf, new, p):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (0, p.astype(jnp.int32), 0, 0))

        kbuf = apply(write, kbuf, k, pos, op_name="kv_write")
        vbuf = apply(write, vbuf, v, pos, op_name="kv_write")
        L = kbuf.shape[1]

        def mk_mask(p):
            rows = p.astype(jnp.int32) + jnp.arange(s)[:, None]
            cols = jnp.arange(L)[None, :]
            return jnp.where(cols <= rows, 0.0, -1e30)[None, None]  # [1,1,s,L]

        mask = apply(mk_mask, pos, op_name="kv_mask")
        out = F.scaled_dot_product_attention(q, kbuf, vbuf, attn_mask=mask)
        out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(out), (kbuf, vbuf, pos + s)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(m, h, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        if x.shape[1] == 1 and not _mp_active():
            # decode step: gate|up as ONE streamed weight (concat hoisted
            # out of the decode scan; measured 621→773 GB/s)
            m = self.gate_proj.weight.shape[1]

            def gu_fused(hv, wg, wu):
                w = jnp.concatenate([wg, wu], axis=1)
                return hv @ w.astype(hv.dtype)

            gu = apply(gu_fused, x, self.gate_proj.weight, self.up_proj.weight,
                       op_name="gate_up_fused")
            return self.down_proj(F.silu(gu[:, :, :m]) * gu[:, :, m:])
        # a step of more than one token: two products, then the activation as
        # ONE op with its own backward (with the rotation's, 32,786 against
        # 30,387 tokens/s on mistral7b.train.pretrain-2k; gate|up joined
        # into a kernel that reads the halves read 32,455: PR 50)
        gated = apply(swiglu_fused, self.gate_proj(x), self.up_proj(x), op_name="swiglu")
        return self.down_proj(gated)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, hidden, cos, sin, attn_mask=None, cache=None):
        residual = hidden
        with jax.named_scope("norm"):
            normed = self.input_layernorm(hidden)
        attn_out = self.self_attn(normed, cos, sin, attn_mask, cache)
        if cache is not None:
            attn_out, new_cache = attn_out
        hidden = residual + attn_out
        with jax.named_scope("norm"):
            normed = self.post_attention_layernorm(hidden)
        with jax.named_scope("mlp"):
            hidden = hidden + self.mlp(normed)
        if cache is not None:
            return hidden, new_cache
        return hidden


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        cos, sin = _rope_cache(config)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, caches=None):
        with jax.named_scope("embed"):
            hidden = self.embed_tokens(input_ids)
            if self.config.dtype == "bfloat16":
                hidden = hidden.astype("bfloat16")
        hcg = _hcg()
        if hcg is not None and hcg.axis_size("sep") > 1 and caches is None:
            sep = hcg.axis_size("sep")
            if input_ids.shape[1] % sep != 0:
                raise ValueError(
                    f"sequence length {input_ids.shape[1]} must be divisible by "
                    f"sep_degree={sep} for sequence parallelism (pad the batch; "
                    "XLA needs static equal shards)"
                )
            # sequence parallelism: shard activations [B, S, H] on (dp, sep)
            from jax.sharding import NamedSharding, PartitionSpec

            b_ax = "dp" if hcg.axis_size("dp") > 1 else None
            sharding = NamedSharding(hcg.mesh, PartitionSpec(b_ax, "sep", None))
            hidden = apply(lambda v: jax.lax.with_sharding_constraint(v, sharding),
                           hidden, op_name="sep_shard")
        cos, sin = self._buffers["rope_cos"], self._buffers["rope_sin"]
        new_caches = []
        use_recompute = self.config.recompute and caches is None and self.training
        for i, layer in enumerate(self.layers):
            if caches is not None:
                hidden, c = layer(hidden, cos, sin, attn_mask, caches[i])
                new_caches.append(c)
            elif use_recompute:
                from ..distributed.fleet.utils.recompute import recompute

                if attn_mask is None:
                    hidden = recompute(layer, hidden, cos, sin)
                else:
                    hidden = recompute(layer, hidden, cos, sin, attn_mask)
            else:
                hidden = layer(hidden, cos, sin, attn_mask)
        with jax.named_scope("norm"):
            hidden = self.norm(hidden)
        if caches is not None:
            return hidden, new_caches
        return hidden


class LlamaForCausalLM(nn.Layer):
    supports_static_kv_cache = True  # 3-tuple (k_buf, v_buf, pos) ring decode

    def __init__(self, config: LlamaConfig):
        with SetupSpan("model.init", family=type(self).__name__,
                       dtype=config.dtype) as span:
            super().__init__()
            self.config = config
            self.llama = LlamaModel(config)
            if config.tie_word_embeddings:
                self.lm_head = None
            else:
                self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                                    has_bias=False, gather_output=True)
            span.note(parameters=self.num_params)

    def forward(self, input_ids, attn_mask=None, caches=None):
        out = self.llama(input_ids, attn_mask, caches)
        hidden = out[0] if caches is not None else out
        with jax.named_scope("head"):
            if self.lm_head is None:
                logits = F.linear(hidden, Tensor(self.llama.embed_tokens.weight._value.T,
                                                 stop_gradient=self.llama.embed_tokens.weight.stop_gradient))
            else:
                logits = self.lm_head(hidden)
        if caches is not None:
            return logits, out[1]
        return logits

    def pretraining_loss(self, input_ids, labels=None, n_chunks: int = 8):
        """Shifted next-token loss via the fused chunked head (no [N, V]
        logits in HBM). Numerically equals LlamaPretrainingCriterion(
        self(ids), ids) up to fp32-accumulated matmul precision."""
        if labels is None:
            labels = input_ids
        hidden = self.llama(input_ids)
        if self.lm_head is None:
            w = Tensor(self.llama.embed_tokens.weight._value.T,
                       stop_gradient=self.llama.embed_tokens.weight.stop_gradient)
        else:
            w = self.lm_head.weight
        with jax.named_scope("loss"):
            return apply(lambda h, wv, y: _chunked_lm_loss(h, wv, y, n_chunks),
                         hidden, w, labels, op_name="fused_lm_loss")

    @property
    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    # ---------------------------------------------- what a serving engine asks
    # (inference/serving_model.py: weights, cache specification, trunk, rope)
    def serving_weights(self, dtype):
        def v(t):
            return t._value.astype(dtype)

        lm = self.llama
        w = {
            "embed": v(self.llama.embed_tokens.weight),
            "norm": v(lm.norm.weight),
        }
        if self.lm_head is None:
            w["head"] = w["embed"].T
        else:
            w["head"] = v(self.lm_head.weight)
        w["layers"] = []
        for layer in lm.layers:
            a, m = layer.self_attn, layer.mlp
            w["layers"].append({
                "ln1": v(layer.input_layernorm.weight),
                "ln2": v(layer.post_attention_layernorm.weight),
                "wq": v(a.q_proj.weight), "wk": v(a.k_proj.weight),
                "wv": v(a.v_proj.weight), "wo": v(a.o_proj.weight),
                "wg": v(m.gate_proj.weight), "wu": v(m.up_proj.weight),
                "wd": v(m.down_proj.weight),
            })
        return w

    def serving_cache_spec(self):
        """Keys and values a kv-head: two ``[nb, KV, bs, D]`` arrays a layer."""
        from ..inference.serving_model import CacheSpec

        cfg = self.config
        KV, D = cfg.num_key_value_heads, cfg.head_dim

        def block(bs):
            return (KV, bs, D)

        return CacheSpec(
            arrays=(("k", block), ("v", block)), layers=cfg.num_hidden_layers,
            key=("llama", cfg.num_attention_heads, KV, D, cfg.hidden_size,
                 float(cfg.rms_norm_eps)),
            kv_heads=KV, head_dim=D)

    def serving_rope(self, max_seq_len):
        cfg = self.config
        d = cfg.head_dim
        inv = 1.0 / (cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
        t = np.arange(max_seq_len, dtype=np.float64)
        fr = np.outer(t, inv)
        # blha rope layout [2, Br=1, Smax, 1, D/2]; llama uses the
        # half-split (neox) rotation (apply_rotary_pos_emb above)
        return jnp.asarray(
            np.stack([np.cos(fr), np.sin(fr)])[:, None, :, None, :],
            jnp.float32)

    def serving_trunk(self, *, block_size, cache_quant="none"):
        from ..ops.paged_attention import blha_attention, paged_counts

        cfg = self.config
        H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        eps = cfg.rms_norm_eps
        bs = block_size

        def rms(x, w):
            xf = x.astype(jnp.float32)
            nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            return (nrm * w.astype(jnp.float32)).astype(x.dtype)

        quant = cache_quant

        def trunk(weights, caches, rope, token_ids,
                  enc, dec, now, cu, bt, mq, scales=None):
            # mq (static): the most tokens one row may feed this step — T for
            # the prefill step, the chunk width in the mixed scan, spec_k + 1
            # for verification, 1 for pure decode steps.  It bounds a CHUNK
            # row's queries only: attention runs rows that feed one token in
            # tiles and rows that feed a chunk one at a time, so a row costs
            # what it holds and no row is padded to mq (blha_attention).  The
            # trunk runs embed -> layers -> final rms and returns the FULL
            # hidden sequence: the engine heads each slot's last packed
            # token, or every draft position (spec verify).
            key_caches, value_caches = caches
            with jax.named_scope("embed"):
                hidden = weights["embed"][token_ids]  # [T, E]
            new_scales = []
            for li, lw in enumerate(weights["layers"]):
                with jax.named_scope("norm"):
                    h = rms(hidden, lw["ln1"])
                with jax.named_scope("attn_proj"):
                    q = h @ lw["wq"]
                    k = h @ lw["wk"]
                    v = h @ lw["wv"]
                    qkv = jnp.concatenate([q, k, v], axis=-1)
                sc = scales[li] if scales is not None else {}
                out, kc, vc, kq, vq, kd, vd = blha_attention(
                    qkv, key_caches[li], value_caches[li], enc, dec, now,
                    cu, bt, num_heads=H, kv_num_heads=KV, head_dim=D,
                    block_size=bs, max_q_len=mq, use_neox_style=True,
                    compute_dtype=hidden.dtype, rope_emb=rope,
                    cache_quant=quant if quant != "int8" else "dynamic",
                    cache_k_quant_scales=sc.get("kq"),
                    cache_v_quant_scales=sc.get("vq"),
                    cache_k_dequant_scales=sc.get("kd"),
                    cache_v_dequant_scales=sc.get("vd"))
                key_caches[li] = kc
                value_caches[li] = vc
                if scales is not None:
                    new_scales.append({"kq": kq, "vq": vq, "kd": kd, "vd": vd})
                with jax.named_scope("attn_out"):
                    hidden = hidden + out @ lw["wo"]
                with jax.named_scope("norm"):
                    h2 = rms(hidden, lw["ln2"])
                with jax.named_scope("mlp"):
                    g = h2 @ lw["wg"]
                    u = h2 @ lw["wu"]
                    hidden = hidden + (jax.nn.silu(g) * u) @ lw["wd"]
            with jax.named_scope("norm"):
                hidden = rms(hidden, weights["norm"])
            # ONE layer's counts an iteration: the layers read and are written alike
            return hidden, (key_caches, value_caches), new_scales, paged_counts(
                hidden.dtype, key_caches[0], dec, now, cu, bt, tokens=token_ids.shape[0],
                heads=H, max_q_len=mq, plain=quant == "none")

        return trunk


# ------------------------------------------------- pipeline-parallel mapping
class _PipeEmbed(nn.Layer):
    """Stage-0 module: token embedding (+ bf16 cast) — single-tensor
    in/out as the pipeline engine requires."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)

    def forward(self, input_ids):
        hidden = self.embed_tokens(input_ids)
        if self.config.dtype == "bfloat16":
            hidden = hidden.astype("bfloat16")
        return hidden


class _PipeDecoder(nn.Layer):
    """One decoder layer owning its own rope cache (stages are independent
    modules; the cache is deterministic from the config)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.block = LlamaDecoderLayer(config)
        cos, sin = _rope_cache(config)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, hidden):
        return self.block(hidden, self._buffers["rope_cos"], self._buffers["rope_sin"])


class _PipeHead(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                            has_bias=False, gather_output=True)

    def forward(self, hidden):
        return self.lm_head(self.norm(hidden))


class _PipeNorm(nn.Layer):
    """Final RMSNorm as its own tail stage piece (used with tied embeddings,
    where the logits matmul reuses the embedding weight)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, hidden):
        return self.norm(hidden)


def _tied_logits(embed_layer, hidden):
    """SharedLayerDesc forward_func for the tail occurrence of the shared
    embedding: logits = hidden @ Wᵉᵐᵇᵀ (reference GPT tied-head contract,
    pp_layers.py SharedLayerDesc:76)."""
    from .. import matmul

    w = embed_layer.embed_tokens.weight
    return matmul(hidden.astype(w.dtype), w, transpose_y=True)


def llama_pipeline_descs(config: LlamaConfig, tie_embeddings: bool = False):
    """LayerDescs for fleet's PipelineLayer: [embed] + L×[decoder] + [head].

    Compose with pp via ``PipelineLayer(layers=llama_pipeline_descs(cfg),
    num_stages=pp, loss_fn=...)`` under a hybrid dp×pp×mp mesh — the TP
    layers inside each stage shard on the stage's mp submesh (the 4-D hybrid
    of BASELINE's GPT-3 rung).

    ``tie_embeddings=True`` shares ONE embedding layer between the stage-0
    lookup and the last-stage logits head via SharedLayerDesc — the compiled
    pipeline psums its gradient across both uses (the reference's
    shared-grad allreduce)."""
    from ..distributed.fleet.meta_parallel import LayerDesc, SharedLayerDesc

    decoders = [LayerDesc(_PipeDecoder, config)
                for _ in range(config.num_hidden_layers)]
    if tie_embeddings:
        return ([SharedLayerDesc("embed", _PipeEmbed, None, "weight", config)]
                + decoders
                + [LayerDesc(_PipeNorm, config),
                   SharedLayerDesc("embed", _PipeEmbed, _tied_logits, "weight",
                                   config)])
    return [LayerDesc(_PipeEmbed, config)] + decoders + [LayerDesc(_PipeHead, config)]


class LlamaPretrainingCriterion(nn.Layer):
    """Shifted next-token CE (PaddleNLP criterion parity)."""

    def __init__(self, config: Optional[LlamaConfig] = None):
        super().__init__()

    def forward(self, logits, labels):
        with jax.named_scope("loss"):
            shift_logits = logits[:, :-1, :]
            shift_labels = labels[:, 1:]
            return F.cross_entropy(
                M.reshape(shift_logits, [-1, shift_logits.shape[-1]]),
                M.reshape(shift_labels, [-1]),
            )


def _chunked_lm_loss(hidden, w, labels, n_chunks: int):
    """Fused lm_head + shifted CE without materializing [N, V] logits.

    Tokens stream through in n_chunks slices; each slice's logits + fp32
    logsumexp live only inside a rematerialized (jax.checkpoint) chunk, so
    peak memory is O(N·V/n_chunks) instead of O(N·V) — the TPU analog of the
    reference's fused parallel cross-entropy
    (fleet/layers/mpu/mp_layers.py ParallelCrossEntropy + PaddleNLP's fused
    head-loss path)."""
    from jax.scipy.special import logsumexp

    B, S, H = hidden.shape
    sh = hidden[:, :-1, :].reshape(-1, H)
    sl = labels[:, 1:].reshape(-1).astype(jnp.int32)
    N = sh.shape[0]
    pad = (-N) % n_chunks
    if pad:
        sh = jnp.concatenate([sh, jnp.zeros((pad, H), sh.dtype)])
        sl = jnp.concatenate([sl, jnp.full((pad,), -1, sl.dtype)])
    hs = sh.reshape(n_chunks, -1, H)
    ys = sl.reshape(n_chunks, -1)

    def chunk_sum(h_c, y_c):
        logits = jax.lax.dot_general(
            h_c, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        lse = logsumexp(logits, axis=-1)
        valid = y_c >= 0
        tgt = jnp.take_along_axis(logits, jnp.maximum(y_c, 0)[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(valid, lse - tgt, 0.0)), jnp.sum(valid)

    def body(carry, xy):
        tot, cnt = carry
        s, c = jax.checkpoint(chunk_sum)(*xy)
        return (tot + s, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.int32(0)), (hs, ys))
    return tot / jnp.maximum(cnt.astype(jnp.float32), 1.0)
