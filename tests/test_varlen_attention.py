"""Varlen / sparse-mask flash attention tests:
parity vs a dense-mask oracle and a packed-2-sequences training test."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn.functional as F
from paddle_tpu.nn.functional.extra import (
    flash_attention_with_sparse_mask,
    flash_attn_varlen_qkvpacked,
)
from paddle_tpu.nn.functional.flash_attention import flash_attn_unpadded

pytestmark = pytest.mark.quick


def dense_oracle(q, k, v, mask, scale):
    """q/k/v [B,H,S,D]; additive mask [B,H,Sq,Sk]; fp64 softmax."""
    logits = np.einsum("bhid,bhjd->bhij", q.astype(np.float64),
                       k.astype(np.float64)) * scale + mask
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhij,bhjd->bhid", w, v.astype(np.float64))


class TestFlashAttnUnpadded:
    def test_parity_vs_dense_mask(self):
        rng = np.random.RandomState(0)
        lens = [5, 9, 3]
        H, D = 4, 16
        total = sum(lens)
        cu = np.zeros(len(lens) + 1, np.int32)
        cu[1:] = np.cumsum(lens)
        q = rng.randn(total, H, D).astype(np.float32)
        k = rng.randn(total, H, D).astype(np.float32)
        v = rng.randn(total, H, D).astype(np.float32)
        scale = 1.0 / np.sqrt(D)
        out, _ = flash_attn_unpadded(
            P.to_tensor(q), P.to_tensor(k), P.to_tensor(v),
            P.to_tensor(cu), P.to_tensor(cu), max(lens), max(lens),
            scale, causal=True)
        out = np.asarray(out.numpy())
        # oracle per sequence
        for b, L in enumerate(lens):
            s = cu[b]
            qb = q[s:s + L].transpose(1, 0, 2)[None]
            kb = k[s:s + L].transpose(1, 0, 2)[None]
            vb = v[s:s + L].transpose(1, 0, 2)[None]
            mask = np.where(np.tril(np.ones((L, L), bool)), 0.0, -1e30)[None, None]
            ref = dense_oracle(qb, kb, vb, mask, scale)[0].transpose(1, 0, 2)
            np.testing.assert_allclose(out[s:s + L], ref, rtol=2e-4, atol=2e-4)

    def test_gqa_and_cross_lengths(self):
        rng = np.random.RandomState(1)
        H, KV, D = 4, 2, 8
        lens_q, lens_k = [3, 6], [7, 10]
        cu_q = np.array([0, 3, 9], np.int32)
        cu_k = np.array([0, 7, 17], np.int32)
        q = rng.randn(9, H, D).astype(np.float32)
        k = rng.randn(17, KV, D).astype(np.float32)
        v = rng.randn(17, KV, D).astype(np.float32)
        scale = 0.3
        out, _ = flash_attn_unpadded(
            P.to_tensor(q), P.to_tensor(k), P.to_tensor(v),
            P.to_tensor(cu_q), P.to_tensor(cu_k), 6, 10, scale, causal=True)
        out = np.asarray(out.numpy())
        for b in range(2):
            Lq, Lk = lens_q[b], lens_k[b]
            sq, sk = cu_q[b], cu_k[b]
            qb = np.repeat(q[sq:sq + Lq].transpose(1, 0, 2)[None], 1, 1)
            kb = np.repeat(k[sk:sk + Lk], H // KV, axis=1).transpose(1, 0, 2)[None]
            vb = np.repeat(v[sk:sk + Lk], H // KV, axis=1).transpose(1, 0, 2)[None]
            # bottom-right causal alignment
            off = Lk - Lq
            m = np.where(np.tril(np.ones((Lq, Lk), bool), k=off), 0.0, -1e30)
            ref = dense_oracle(qb.transpose(0, 2, 1, 3).transpose(0, 1, 2, 3)
                               if False else qb, kb, vb,
                               m[None, None], scale)[0].transpose(1, 0, 2)
            np.testing.assert_allclose(out[sq:sq + Lq], ref, rtol=2e-4,
                                       atol=2e-4)


class TestVarlenQkvPacked:
    def test_padded_layout_parity(self):
        rng = np.random.RandomState(2)
        B, S, H, KV, D = 2, 8, 4, 2, 8
        lens = np.array([5, 8], np.int32)
        cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        G = H // KV + 2
        qkv = rng.randn(B * S, G, KV, D).astype(np.float32)
        out, _ = flash_attn_varlen_qkvpacked(
            P.to_tensor(qkv), P.to_tensor(cu), P.to_tensor(cu), S, S,
            1.0 / np.sqrt(D), causal=True, varlen_padded=True)
        out = np.asarray(out.numpy())
        assert out.shape == (B * S, H, D)
        for b in range(B):
            L = int(lens[b])
            blk = qkv[b * S:(b + 1) * S]
            q = blk[:L, :G - 2].reshape(L, H, D).transpose(1, 0, 2)[None]
            k = np.repeat(blk[:L, G - 2], H // KV, 1).transpose(1, 0, 2)[None]
            v = np.repeat(blk[:L, G - 1], H // KV, 1).transpose(1, 0, 2)[None]
            m = np.where(np.tril(np.ones((L, L), bool)), 0.0, -1e30)[None, None]
            ref = dense_oracle(q, k, v, m, 1.0 / np.sqrt(D))[0].transpose(1, 0, 2)
            np.testing.assert_allclose(out[b * S:b * S + L], ref,
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(out[b * S + L:(b + 1) * S], 0.0)

    def test_packed_two_sequences_training(self):
        """Done-criterion: train through the varlen path with two
        packed sequences — grads flow and the loss drops."""
        rng = np.random.RandomState(3)
        H, D, E = 2, 8, 16
        lens = [6, 4]
        total = sum(lens)
        cu = np.array([0, 6, 10], np.int32)
        lin_qkv = P.to_tensor(rng.randn(E, 3 * H * D).astype(np.float32) * 0.1)
        lin_qkv.stop_gradient = False
        x = P.to_tensor(rng.randn(total, E).astype(np.float32))
        y = P.to_tensor(rng.randn(total, H * D).astype(np.float32) * 0.1)
        losses = []
        for it in range(12):
            qkv = P.matmul(x, lin_qkv)
            q, k, v = (P.reshape(t, [total, H, D])
                       for t in P.split(qkv, 3, axis=1))
            out, _ = flash_attn_unpadded(
                q, k, v, P.to_tensor(cu), P.to_tensor(cu), max(lens),
                max(lens), 1.0 / np.sqrt(D), causal=True)
            loss = P.mean((P.reshape(out, [total, H * D]) - y) ** 2)
            loss.backward()
            g = lin_qkv.grad
            assert g is not None and np.isfinite(np.asarray(g.numpy())).all()
            lin_qkv = P.to_tensor(np.asarray(lin_qkv.numpy())
                                  - 0.5 * np.asarray(g.numpy()))
            lin_qkv.stop_gradient = False
            losses.append(float(np.asarray(loss.numpy())))
        assert losses[-1] < losses[0] * 0.9

    def test_cross_sequence_isolation(self):
        """Tokens of one packed sequence must not see the other: perturbing
        sequence 2 leaves sequence 1's outputs bit-identical."""
        rng = np.random.RandomState(4)
        H, D = 2, 8
        cu = np.array([0, 5, 9], np.int32)
        q = rng.randn(9, H, D).astype(np.float32)
        k = rng.randn(9, H, D).astype(np.float32)
        v = rng.randn(9, H, D).astype(np.float32)
        out1, _ = flash_attn_unpadded(P.to_tensor(q), P.to_tensor(k),
                                      P.to_tensor(v), P.to_tensor(cu),
                                      P.to_tensor(cu), 5, 5,
                                      1.0 / np.sqrt(D), causal=True)
        k2, v2 = k.copy(), v.copy()
        k2[5:] += 3.0
        v2[5:] -= 2.0
        out2, _ = flash_attn_unpadded(P.to_tensor(q), P.to_tensor(k2),
                                      P.to_tensor(v2), P.to_tensor(cu),
                                      P.to_tensor(cu), 5, 5,
                                      1.0 / np.sqrt(D), causal=True)
        np.testing.assert_array_equal(np.asarray(out1.numpy())[:5],
                                      np.asarray(out2.numpy())[:5])


class TestSparseMaskAttention:
    def test_parity_vs_dense_mask(self):
        rng = np.random.RandomState(5)
        B, S, H, D = 2, 12, 2, 8
        q = rng.randn(B, S, H, D).astype(np.float32)
        k = rng.randn(B, S, H, D).astype(np.float32)
        v = rng.randn(B, S, H, D).astype(np.float32)
        # per-column mask start rows in [j+1, S+1] (masked at i >= start)
        start = rng.randint(1, S + 1, (B, H, S)).astype(np.int32)
        start = np.maximum(start, np.arange(1, S + 1)[None, None, :])
        out = flash_attention_with_sparse_mask(
            P.to_tensor(q), P.to_tensor(k), P.to_tensor(v),
            P.to_tensor(start), is_causal=True)
        mask = np.full((B, H, S, S), -1e30)
        for b in range(B):
            for h in range(H):
                for j in range(S):
                    for i in range(S):
                        if i >= j and i < start[b, h, j]:
                            mask[b, h, i, j] = 0.0
        ref = dense_oracle(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), mask, 1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   ref.transpose(0, 2, 1, 3),
                                   rtol=2e-4, atol=2e-4)
