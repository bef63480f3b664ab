"""Mixture-of-Experts (parity:
/root/reference/python/paddle/incubate/distributed/models/moe/moe_layer.py:263
MoELayer + gating ops number_count/limit_by_capacity/prune_gate_by_capacity/
random_routing kernels).

TPU-native: GShard-style dense dispatch — routing becomes one-hot einsums and
the token shuffle becomes an all-to-all XLA inserts when expert weights are
sharded on the expert axis of the mesh. Capacity-factor token dropping matches
the reference's limit_by_capacity semantics.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..... import nn
from .....nn import functional as F
from .....ops.dispatch import apply
from .....tensor.tensor import Tensor
from .....distributed.topology import get_hybrid_communicate_group

__all__ = ["MoELayer", "GShardGate", "SwitchGate", "NaiveGate"]


class NaiveGate(nn.Layer):
    """Linear router (parity: gate/naive_gate.py)."""

    def __init__(self, d_model, num_experts):
        super().__init__()
        self.weight = self.create_parameter([d_model, num_experts])

    def forward(self, x):
        return F.linear(x, self.weight)


class GShardGate(NaiveGate):
    top_k = 2


class SwitchGate(NaiveGate):
    top_k = 1


class MoELayer(nn.Layer):
    """Top-k routed expert FFN bank.

    Experts are a stacked weight bank [E, ...] sharded on ``expert_axis`` of
    the active mesh ('mp' by default — the reference's moe group rides its mp
    group too unless a dedicated group is passed).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2, capacity_factor=1.25,
                 gate: Optional[nn.Layer] = None, expert_axis=None, activation="gelu",
                 group=None, recompute_interval=0, name=None, dispatch_mode="ragged"):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.dispatch_mode = dispatch_mode  # "ragged" (sort-based) | "dense"
        self.gate = gate or NaiveGate(d_model, num_experts)
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden])
        self.b1 = self.create_parameter([num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model])
        self.b2 = self.create_parameter([num_experts, 1, d_model], is_bias=True)
        hcg = get_hybrid_communicate_group()
        # expert placement: the dedicated 'ep' axis when active (explicit
        # all-to-all dispatch, reference moe group analog), else 'mp' reuse
        # (GSPMD-auto sharding of the expert bank) — reuse documented in the
        # class docstring
        if expert_axis is None:
            expert_axis = "ep" if (hcg is not None and hcg.axis_size("ep") > 1) else "mp"
        self.expert_axis = expert_axis
        self._ep_size = 1
        self._ep_fn_cache = {}
        if hcg is not None and hcg.axis_size(expert_axis) > 1:
            mesh = hcg.mesh
            self._mesh = mesh
            self._ep_size = hcg.axis_size(expert_axis)
            if num_experts % self._ep_size != 0:
                raise ValueError(
                    f"num_experts={num_experts} must be a multiple of the "
                    f"'{expert_axis}' axis size {self._ep_size}")
            for p in (self.w1, self.b1, self.w2, self.b2):
                if not isinstance(p._value, jax.core.Tracer):
                    spec = PartitionSpec(expert_axis, *([None] * (p.ndim - 1)))
                    p._value = jax.device_put(p._value, NamedSharding(mesh, spec))

    def forward(self, x):
        """x: [B, S, d] (or [N, d]). Returns same shape + aux loss stored on
        ``self.l_aux`` (load-balancing, Switch/GShard style)."""
        orig_shape = x.shape
        squeeze_back = len(orig_shape) == 3
        gate_logits = self.gate(x)

        E, K = self.num_experts, self.top_k
        cap_factor = self.capacity_factor
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu}[self.activation]

        mode = self.dispatch_mode

        def f_ragged(xv, gv, w1, b1, w2, b2):
            """Sort-based ragged routing (reference
            analog: the global_scatter/global_gather all-to-all of
            moe_layer.py:263). No [N, E, C] combine tensor: token slots are
            sorted by expert, scattered into the [E*C, d] expert buffer,
            expert FFNs run as batched [E, C, ...] matmuls, results gather
            back by the same permutation. Priority and capacity-drop
            semantics are identical to the dense path (slot-major)."""
            xt = xv.reshape(-1, xv.shape[-1])  # [N, d]
            gt = gv.reshape(-1, E).astype(jnp.float32)
            N = xt.shape[0]
            C = max(int(math.ceil(N / E * cap_factor * K)), 1)
            probs = jax.nn.softmax(gt, axis=-1)
            topw, topi = jax.lax.top_k(probs, K)  # [N, K]
            topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

            # slot-major flatten: all slot-0 assignments first (GShard
            # priority), then slot 1, ...
            flat_e = topi.T.reshape(-1)                       # [NK]
            flat_w = topw.T.reshape(-1).astype(xt.dtype)
            flat_tok = jnp.tile(jnp.arange(N), K)
            order = jnp.argsort(flat_e, stable=True)          # group by expert
            se = flat_e[order]
            stok = flat_tok[order]
            sw = flat_w[order]
            counts = jnp.bincount(flat_e, length=E)
            start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                     jnp.cumsum(counts)[:-1]])
            pos = jnp.arange(N * K) - jnp.take(start, se)     # rank within expert
            keep = pos < C
            dest = jnp.where(keep, se * C + pos, E * C)       # dropped -> dummy row
            buf = jnp.zeros((E * C + 1, xt.shape[-1]), xt.dtype)
            buf = buf.at[dest].set(jnp.take(xt, stok, axis=0))
            exp_in = buf[:-1].reshape(E, C, -1)
            h = act(jnp.einsum("ecd,edh->ech", exp_in, w1) + b1)
            exp_out = (jnp.einsum("ech,ehd->ecd", h, w2) + b2).reshape(E * C, -1)
            exp_out = jnp.concatenate([exp_out, jnp.zeros_like(exp_out[:1])])
            token_out = jnp.take(exp_out, dest, axis=0) * sw[:, None]
            out = jnp.zeros_like(xt).at[stok].add(
                jnp.where(keep[:, None], token_out, 0))
            me = probs.mean(0)
            ce = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32).mean(0)
            l_aux = E * jnp.sum(me * ce)
            return out.reshape(xv.shape), l_aux

        def f(xv, gv, w1, b1, w2, b2):
            xt = xv.reshape(-1, xv.shape[-1])  # [N, d]
            gt = gv.reshape(-1, E).astype(jnp.float32)
            N = xt.shape[0]
            C = max(int(math.ceil(N / E * cap_factor * K)), 1)
            probs = jax.nn.softmax(gt, axis=-1)
            topw, topi = jax.lax.top_k(probs, K)  # [N, K]
            topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

            combine = jnp.zeros((N, E, C), jnp.float32)
            # GShard priority assignment: capacity positions are allocated
            # jointly across top-k slots (slot 0 first), so two tokens routed
            # to the same expert via different slots never share a slot.
            counts = jnp.zeros((E,), jnp.int32)
            for slot in range(K):
                e = topi[:, slot]  # [N]
                onehot = jax.nn.one_hot(e, E, dtype=jnp.int32)  # [N, E]
                pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based positions per expert (this slot)
                pos_tok = jnp.sum(pos, axis=-1) - 1 + jnp.take(counts, e)  # offset by prior slots
                keep = pos_tok < C  # capacity drop (limit_by_capacity parity)
                cpos = jnp.clip(pos_tok, 0, C - 1)
                oh_c = jax.nn.one_hot(cpos, C, dtype=jnp.float32) * keep[:, None]
                combine = combine + topw[:, slot, None, None] * onehot[..., None] * oh_c[:, None, :]
                counts = counts + jnp.sum(onehot, axis=0)
            dispatch = (combine > 0).astype(xt.dtype)  # [N, E, C]
            exp_in = jnp.einsum("nec,nd->ecd", dispatch, xt)
            h = act(jnp.einsum("ecd,edh->ech", exp_in, w1) + b1)
            exp_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2
            out = jnp.einsum("nec,ecd->nd", combine.astype(xt.dtype), exp_out)
            # load-balance aux loss (GShard): E * sum(fraction_tokens * fraction_probs)
            me = probs.mean(0)
            ce = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32).mean(0)
            l_aux = E * jnp.sum(me * ce)
            return out.reshape(xv.shape), l_aux

        def f_ep(xv, gv, w1, b1, w2, b2):
            """Expert-parallel ragged dispatch over the 'ep' mesh axis —
            manual shard_map: each ep rank routes ITS token shard into a
            per-expert capacity buffer, a ``lax.all_to_all`` exchanges the
            buffers so every rank receives the tokens bound for its local
            experts (from all source ranks), the batched expert FFN runs,
            and a reverse all_to_all returns results to the token owners
            (reference: global_scatter/global_gather of moe_layer.py:263).
            Capacity is per (expert, source-rank): C_local = ceil(N_local /
            E · cf · K), so total capacity matches the single-device path;
            drops are decided rank-locally, exactly the reference's
            per-worker limit_by_capacity."""
            ep = self._ep_size
            E_local = E // ep

            def local(xl, gl, w1l, b1l, w2l, b2l):
                xt = xl.reshape(-1, xl.shape[-1])           # [N_local, d]
                gt = gl.reshape(-1, E).astype(jnp.float32)
                N = xt.shape[0]
                C = max(int(math.ceil(N / E * cap_factor * K)), 1)
                probs = jax.nn.softmax(gt, axis=-1)
                topw, topi = jax.lax.top_k(probs, K)
                topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
                flat_e = topi.T.reshape(-1)
                flat_w = topw.T.reshape(-1).astype(xt.dtype)
                flat_tok = jnp.tile(jnp.arange(N), K)
                order = jnp.argsort(flat_e, stable=True)
                se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]
                counts = jnp.bincount(flat_e, length=E)
                start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                         jnp.cumsum(counts)[:-1]])
                pos = jnp.arange(N * K) - jnp.take(start, se)
                keep = pos < C
                dest = jnp.where(keep, se * C + pos, E * C)
                buf = jnp.zeros((E * C + 1, xt.shape[-1]), xt.dtype)
                buf = buf.at[dest].set(jnp.take(xt, stok, axis=0))
                # [E, C, d] -> exchange: each rank sends chunk r (that rank's
                # experts) and receives its own experts' tokens from every
                # source, concatenated on the capacity dim -> [E_local, ep*C, d]
                send = buf[:-1].reshape(E, C, -1)
                recv = jax.lax.all_to_all(send, self.expert_axis,
                                          split_axis=0, concat_axis=1,
                                          tiled=True)
                h = act(jnp.einsum("ecd,edh->ech", recv, w1l) + b1l)
                expert_out = jnp.einsum("ech,ehd->ecd", h, w2l) + b2l
                # reverse exchange: results go back to the source ranks
                back = jax.lax.all_to_all(expert_out, self.expert_axis,
                                          split_axis=1, concat_axis=0,
                                          tiled=True)
                exp_out = back.reshape(E * C, -1)
                exp_out = jnp.concatenate([exp_out, jnp.zeros_like(exp_out[:1])])
                token_out = jnp.take(exp_out, dest, axis=0) * sw[:, None]
                out = jnp.zeros_like(xt).at[stok].add(
                    jnp.where(keep[:, None], token_out, 0))
                me = probs.mean(0)
                ce = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32).mean(0)
                l_aux = jax.lax.pmean(E * jnp.sum(me * ce), self.expert_axis)
                return out.reshape(xl.shape), l_aux

            axis = self.expert_axis
            key = (tuple(xv.shape), str(xv.dtype))
            fn = self._ep_fn_cache.get(key)
            if fn is None:
                tok_spec = PartitionSpec(axis, *([None] * (xv.ndim - 1)))
                w_spec = lambda p: PartitionSpec(axis, *([None] * (p.ndim - 1)))  # noqa: E731
                from .....distributed.shard_map_compat import shard_map_manual

                mapped = shard_map_manual(
                    local, self._mesh,
                    in_specs=(tok_spec, tok_spec, w_spec(self.w1), w_spec(self.b1),
                              w_spec(self.w2), w_spec(self.b2)),
                    out_specs=(tok_spec, PartitionSpec()),
                    manual_axes={axis})
                # partial-manual shard_map needs a surrounding jit scope even
                # for eager calls (auto axes resolve under the abstract mesh)
                fn = jax.jit(mapped)
                self._ep_fn_cache[key] = fn
            return fn(xv, gv, w1, b1, w2, b2)

        if self._ep_size > 1 and self.expert_axis == "ep":
            from .....distributed.shard_map_compat import (
                partial_manual_supported,
            )

            if not partial_manual_supported(self._mesh, {self.expert_axis}):
                # old jax fatally aborts XLA on partial-manual all_to_all
                # next to a size>1 auto axis — refuse cleanly instead
                raise NotImplementedError(
                    "expert-parallel MoE: this jax version cannot mix the "
                    "manual 'ep' axis with size>1 auto mesh axes — use an "
                    "ep-only mesh or a jax with top-level jax.shard_map "
                    "(>=0.8)")
            impl = f_ep
        else:
            impl = f_ragged if mode == "ragged" else f
        out, l_aux = apply(
            lambda *a: tuple(impl(*a)), x, gate_logits, self.w1, self.b1, self.w2, self.b2,
            op_name="moe", n_outs=2,
        )
        self.l_aux = l_aux
        return out
