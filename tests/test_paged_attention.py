"""Parity tests for the paged-KV serving attention
(block_multihead_attention) against a naive dense reference — mirrors the
reference's test matrix (test/legacy_test/test_block_multihead_attention.py:
EncDec, GQA, RoPE, PreCache, cache-KV quant) plus a mixed prefill+decode
batch, which is the continuous-batching serving case."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.incubate.nn.functional import block_multihead_attention
from paddle_tpu.ops.paged_attention import build_padding_metadata

pytestmark = pytest.mark.quick


def naive_attn(q, k, v, cache_k=None, cache_v=None, pre_k=None, pre_v=None,
               mask=None, causal=False):
    """Dense attention oracle: q [B,H,S,D], k/v [B,KV,S,D]; caches
    [B,KV,L,D] prepend along the key axis; fp32 softmax; GQA tiles KV
    heads."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    rep = H // KV

    def expand(x):
        return np.repeat(x, rep, axis=1) if x.shape[1] != H else x

    keys = expand(k)
    vals = expand(v)
    offset = 0
    if cache_k is not None:
        keys = np.concatenate([expand(cache_k), keys], axis=2)
        vals = np.concatenate([expand(cache_v), vals], axis=2)
        offset = cache_k.shape[2]
    pre = 0
    if pre_k is not None:
        keys = np.concatenate([expand(pre_k), keys], axis=2)
        vals = np.concatenate([expand(pre_v), vals], axis=2)
        pre = pre_k.shape[2]
    logits = np.einsum("bhsd,bhld->bhsl", q.astype(np.float64),
                       keys.astype(np.float64)) / np.sqrt(D)
    if causal:
        L = keys.shape[2]
        qpos = offset + np.arange(S)
        kpos = np.arange(L) - pre
        viz = kpos[None, :] <= qpos[:, None]
        logits = np.where(viz[None, None], logits, -1e30)
    if mask is not None:
        logits = logits + mask.astype(np.float64)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhsl,bhld->bhsd", w, vals.astype(np.float64))


def pack_qkv(q, k, v):
    """[B,H,S,D]x3 -> [sum(S), (H+2KV)D] packed tokens (all seqs full S)."""
    B, H, S, D = q.shape
    KV = k.shape[1]

    def flat(x, nh):
        return x.transpose(0, 2, 1, 3).reshape(B * S, nh * D)

    return np.concatenate([flat(q, H), flat(k, KV), flat(v, KV)], axis=1)


def make_blocks(B, blocks_per_seq):
    """Sequential free-list allocation like the reference test."""
    bt = np.zeros((B, blocks_per_seq), np.int32)
    nxt = 0
    for i in range(B):
        for j in range(blocks_per_seq):
            bt[i, j] = nxt
            nxt += 1
    return bt, nxt


def paged_to_dense(cache, bt, length):
    """[NB,KV,bs,D] + block table row-major -> [B,KV,length,D]."""
    NB, KV, bs, D = cache.shape
    B = bt.shape[0]
    out = np.zeros((B, KV, length, D), np.float32)
    for i in range(B):
        for j in range(length):
            out[i, :, j] = np.asarray(cache[bt[i, j // bs], :, j % bs],
                                      np.float32)
    return out


def run_blha(qkv, kc, vc, enc, dec, now, bt, block_size, **kw):
    _, _, cu, _ = build_padding_metadata(now)
    kc_t, vc_t = P.to_tensor(kc), P.to_tensor(vc)
    out = block_multihead_attention(
        P.to_tensor(qkv), kc_t, vc_t,
        P.to_tensor(np.asarray(enc, np.int32)),
        P.to_tensor(np.asarray(dec, np.int32)),
        P.to_tensor(np.asarray(now, np.int32)),
        None, None, P.to_tensor(cu), P.to_tensor(cu),
        P.to_tensor(bt), block_size=block_size, **kw)
    return (np.asarray(out[0].numpy()), np.asarray(out[2].numpy()),
            np.asarray(out[3].numpy()))


class TestEncDec:
    B, H, S, D, bs = 2, 4, 16, 32, 8

    def setup_method(self, _):
        self.rng = np.random.RandomState(7)
        self.blocks_per_seq = (self.S + 8 + self.bs - 1) // self.bs
        self.bt, self.nb = make_blocks(self.B, self.blocks_per_seq)

    def _rand(self, shape):
        return self.rng.uniform(-1, 1, shape).astype(np.float32)

    def test_prefill_then_decode_parity(self):
        B, H, S, D = self.B, self.H, self.S, self.D
        kc = np.zeros((self.nb, H, self.bs, D), np.float32)
        vc = np.zeros_like(kc)
        q, k, v = self._rand((B, H, S, D)), self._rand((B, H, S, D)), self._rand((B, H, S, D))
        out, kc, vc = run_blha(pack_qkv(q, k, v), kc, vc,
                               [S] * B, [0] * B, [S] * B, self.bt, self.bs)
        ref = naive_attn(q, k, v, causal=True)
        np.testing.assert_allclose(
            out, ref.transpose(0, 2, 1, 3).reshape(B * S, H * D),
            rtol=2e-4, atol=2e-4)
        # the paged cache now holds this step's K/V
        np.testing.assert_allclose(paged_to_dense(kc, self.bt, S),
                                   k, rtol=1e-5, atol=1e-5)

        # --- decode step: 1 token per sequence, random additive tgt_mask
        q1, k1, v1 = (self._rand((B, H, 1, D)) for _ in range(3))
        tgt = self._rand((B, H, 1, S + 1))
        out1, kc, vc = run_blha(pack_qkv(q1, k1, v1), kc, vc,
                                [0] * B, [S] * B, [1] * B, self.bt, self.bs,
                                tgt_mask=P.to_tensor(tgt))
        cache_k = paged_to_dense(kc, self.bt, S)
        cache_v = paged_to_dense(vc, self.bt, S)
        ref1 = naive_attn(q1, k1, v1, cache_k, cache_v, mask=tgt)
        np.testing.assert_allclose(
            out1, ref1.transpose(0, 2, 1, 3).reshape(B, H * D),
            rtol=2e-4, atol=2e-4)

    def test_gqa(self):
        B, H, S, D, KV = self.B, self.H, self.S, self.D, 2
        kc = np.zeros((self.nb, KV, self.bs, D), np.float32)
        vc = np.zeros_like(kc)
        q = self._rand((B, H, S, D))
        k, v = self._rand((B, KV, S, D)), self._rand((B, KV, S, D))
        out, kc2, vc2 = run_blha(pack_qkv(q, k, v), kc, vc,
                                 [S] * B, [0] * B, [S] * B, self.bt, self.bs)
        ref = naive_attn(q, k, v, causal=True)
        np.testing.assert_allclose(
            out, ref.transpose(0, 2, 1, 3).reshape(B * S, H * D),
            rtol=2e-4, atol=2e-4)
        # decode on the GQA cache
        q1 = self._rand((B, H, 1, D))
        k1, v1 = self._rand((B, KV, 1, D)), self._rand((B, KV, 1, D))
        out1, _, _ = run_blha(pack_qkv(q1, k1, v1), kc2, vc2,
                              [0] * B, [S] * B, [1] * B, self.bt, self.bs)
        ck = paged_to_dense(kc2, self.bt, S)[:, :KV]
        cv = paged_to_dense(vc2, self.bt, S)[:, :KV]
        ref1 = naive_attn(q1, k1, v1, ck, cv, causal=True)
        np.testing.assert_allclose(
            out1, ref1.transpose(0, 2, 1, 3).reshape(B, H * D),
            rtol=2e-4, atol=2e-4)

    def test_mixed_prefill_and_decode_one_call(self):
        """Sequence 0 decodes (ctx=S) while sequence 1 prefills — one call,
        outputs match the two phases run against the dense oracle."""
        B, H, S, D = self.B, self.H, self.S, self.D
        kc = np.zeros((self.nb, H, self.bs, D), np.float32)
        vc = np.zeros_like(kc)
        # pre-populate seq 0's context via a normal prefill of both
        q0, k0, v0 = (self._rand((B, H, S, D)) for _ in range(3))
        _, kc, vc = run_blha(pack_qkv(q0, k0, v0), kc, vc,
                             [S] * B, [0] * B, [S] * B, self.bt, self.bs)
        # now: seq0 1 decode token; seq1 re-prefills S2 fresh tokens
        S2 = 6
        qd, kd, vd = (self._rand((1, H, 1, D)) for _ in range(3))
        qp, kp, vp = (self._rand((1, H, S2, D)) for _ in range(3))
        tok0 = np.concatenate([
            qd.transpose(0, 2, 1, 3).reshape(1, H * D),
            kd.transpose(0, 2, 1, 3).reshape(1, H * D),
            vd.transpose(0, 2, 1, 3).reshape(1, H * D)], axis=1)
        tokp = np.concatenate([
            qp.transpose(0, 2, 1, 3).reshape(S2, H * D),
            kp.transpose(0, 2, 1, 3).reshape(S2, H * D),
            vp.transpose(0, 2, 1, 3).reshape(S2, H * D)], axis=1)
        qkv = np.concatenate([tok0, tokp], axis=0)  # [1+S2, 3HD]
        out, kc, vc = run_blha(qkv, kc, vc,
                               [0, S2], [S, 0], [1, S2], self.bt, self.bs)
        # seq 0: decode against its cached context
        ck = paged_to_dense(kc, self.bt, S)[0:1]
        cv = paged_to_dense(vc, self.bt, S)[0:1]
        ref0 = naive_attn(qd, kd, vd, ck, cv, causal=True)
        np.testing.assert_allclose(out[0], ref0.transpose(0, 2, 1, 3).reshape(H * D),
                                   rtol=2e-4, atol=2e-4)
        # seq 1: fresh causal prefill (its block rows were overwritten)
        ref1 = naive_attn(qp, kp, vp, causal=True)
        np.testing.assert_allclose(
            out[1:], ref1.transpose(0, 2, 1, 3).reshape(S2, H * D),
            rtol=2e-4, atol=2e-4)

    def test_rope_interleaved(self):
        """In-kernel rope, reference layout [2, B, Smax, 1, D/2] with
        interleaved (non-neox) rotation."""
        B, H, S, D = self.B, self.H, self.S, self.D
        kc = np.zeros((self.nb, H, self.bs, D), np.float32)
        vc = np.zeros_like(kc)
        q, k, v = (self._rand((B, H, S, D)) for _ in range(3))
        pos = np.arange(S + 8)
        inv = 10000.0 ** (-np.arange(0, D, 2) / D)
        freqs = np.einsum("i,j->ij", pos, inv)  # [Smax, D/2]
        rope = np.stack([np.cos(freqs), np.sin(freqs)])[:, None, :, None, :]
        out, _, _ = run_blha(pack_qkv(q, k, v), kc, vc,
                             [S] * B, [0] * B, [S] * B, self.bt, self.bs,
                             rope_emb=P.to_tensor(rope.astype(np.float32)))

        def rot(x):  # interleaved pairs at absolute position
            c = np.cos(freqs)[:S][None, None]
            s = np.sin(freqs)[:S][None, None]
            x1, x2 = x[..., 0::2], x[..., 1::2]
            o = np.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
            return o.reshape(x.shape)

        qs = rot(q.transpose(0, 1, 2, 3))  # [B,H,S,D] rotate over S axis
        ks = rot(k)
        ref = naive_attn(qs, ks, v, causal=True)
        np.testing.assert_allclose(
            out, ref.transpose(0, 2, 1, 3).reshape(B * S, H * D),
            rtol=2e-4, atol=2e-4)

    def test_pre_cache(self):
        B, H, S, D = self.B, self.H, self.S, self.D
        P_len = 4
        kc = np.zeros((self.nb, H, self.bs, D), np.float32)
        vc = np.zeros_like(kc)
        q, k, v = (self._rand((B, H, S, D)) for _ in range(3))
        pk, pv = self._rand((B, H, P_len, D)), self._rand((B, H, P_len, D))
        out, _, _ = run_blha(pack_qkv(q, k, v), kc, vc,
                             [S] * B, [0] * B, [S] * B, self.bt, self.bs,
                             pre_key_cache=P.to_tensor(pk),
                             pre_value_cache=P.to_tensor(pv))
        ref = naive_attn(q, k, v, pre_k=pk, pre_v=pv, causal=True)
        np.testing.assert_allclose(
            out, ref.transpose(0, 2, 1, 3).reshape(B * S, H * D),
            rtol=2e-4, atol=2e-4)

    def test_qkv_bias_and_int32_dequant(self):
        B, H, S, D = self.B, self.H, 4, self.D
        kc = np.zeros((self.nb, H, self.bs, D), np.float32)
        vc = np.zeros_like(kc)
        q, k, v = (self._rand((B, H, S, D)) for _ in range(3))
        bias = self.rng.uniform(-0.5, 0.5, (3 * H * D,)).astype(np.float32)
        scale = np.full((3 * H * D,), 0.01, np.float32)
        qkv_f = pack_qkv(q, k, v)
        qkv_i = np.round(qkv_f / 0.01).astype(np.int32)
        out, _, _ = run_blha(qkv_i, kc, vc, [S] * B, [0] * B, [S] * B,
                             self.bt, self.bs,
                             qkv_out_scale=P.to_tensor(scale),
                             qkv_bias=P.to_tensor(bias),
                             compute_dtype="fp32")

        def unpack(x, o, nh):
            return x[:, o:o + nh * D].reshape(B, S, nh, D).transpose(0, 2, 1, 3)

        deq = qkv_i.astype(np.float32) * 0.01 + bias[None]
        ref = naive_attn(unpack(deq, 0, H), unpack(deq, H * D, H),
                         unpack(deq, 2 * H * D, H), causal=True)
        np.testing.assert_allclose(
            out, ref.transpose(0, 2, 1, 3).reshape(B * S, H * D),
            rtol=5e-3, atol=5e-3)


class TestCacheQuant:
    B, H, S, D, bs = 2, 4, 16, 32, 8

    def setup_method(self, _):
        self.rng = np.random.RandomState(3)
        self.blocks_per_seq = (self.S + 8 + self.bs - 1) // self.bs
        self.bt, self.nb = make_blocks(self.B, self.blocks_per_seq)

    def _run_quant(self, dynamic):
        B, H, S, D = self.B, self.H, self.S, self.D
        kc = np.zeros((self.nb, H, self.bs, D), np.uint8)
        vc = np.zeros_like(kc)
        q, k, v = (self.rng.uniform(-1, 1, (B, H, S, D)).astype(np.float32)
                   for _ in range(3))
        if dynamic:
            shape = (B, H)
            kq = P.to_tensor(np.zeros(shape, np.float32))
            vq = P.to_tensor(np.zeros(shape, np.float32))
            kd = P.to_tensor(np.zeros(shape, np.float32))
            vd = P.to_tensor(np.zeros(shape, np.float32))
        else:
            kmax = np.abs(k).max(axis=(0, 2, 3)) + 1e-6  # per head
            vmax = np.abs(v).max(axis=(0, 2, 3)) + 1e-6
            kq = P.to_tensor((127.0 / kmax).astype(np.float32))
            vq = P.to_tensor((127.0 / vmax).astype(np.float32))
            kd = P.to_tensor((kmax / 127.0).astype(np.float32))
            vd = P.to_tensor((vmax / 127.0).astype(np.float32))
        out, kc, vc = run_blha(
            pack_qkv(q, k, v), kc, vc, [S] * B, [0] * B, [S] * B,
            self.bt, self.bs, cache_k_quant_scales=kq,
            cache_v_quant_scales=vq, cache_k_dequant_scales=kd,
            cache_v_dequant_scales=vd, use_dynamic_cachekv_quant=dynamic)
        # prefill output itself is full precision
        ref = naive_attn(q, k, v, causal=True)
        np.testing.assert_allclose(
            out, ref.transpose(0, 2, 1, 3).reshape(B * S, H * D),
            rtol=2e-4, atol=2e-4)
        assert kc.dtype == np.uint8
        # decode reads the dequantized cache: compare against dequant oracle
        q1, k1, v1 = (self.rng.uniform(-1, 1, (B, H, 1, D)).astype(np.float32)
                      for _ in range(3))
        out1, _, _ = run_blha(
            pack_qkv(q1, k1, v1), kc, vc, [0] * B, [S] * B, [1] * B,
            self.bt, self.bs, cache_k_quant_scales=kq,
            cache_v_quant_scales=vq, cache_k_dequant_scales=kd,
            cache_v_dequant_scales=vd, use_dynamic_cachekv_quant=dynamic)
        kdv = np.asarray(kd.numpy())
        vdv = np.asarray(vd.numpy())
        if dynamic:
            kdq = (paged_to_dense(kc, self.bt, S) - 128.0) * kdv[:, :, None, None]
            vdq = (paged_to_dense(vc, self.bt, S) - 128.0) * vdv[:, :, None, None]
        else:
            kdq = (paged_to_dense(kc, self.bt, S) - 128.0) * kdv[None, :, None, None]
            vdq = (paged_to_dense(vc, self.bt, S) - 128.0) * vdv[None, :, None, None]
        ref1 = naive_attn(q1, k1, v1, kdq, vdq, causal=True)
        np.testing.assert_allclose(
            out1, ref1.transpose(0, 2, 1, 3).reshape(B, H * D),
            rtol=0.05, atol=0.05)
        # quantization error vs the fp cache stays small
        np.testing.assert_allclose(kdq, k, atol=2.5 / 127.0)

    def test_static_quant(self):
        self._run_quant(dynamic=False)

    def test_dynamic_quant(self):
        self._run_quant(dynamic=True)

    def test_dynamic_quant_writes_scales_inplace(self):
        B, H, S, D = self.B, self.H, self.S, self.D
        kc = np.zeros((self.nb, H, self.bs, D), np.uint8)
        vc = np.zeros_like(kc)
        q, k, v = (self.rng.uniform(-1, 1, (B, H, S, D)).astype(np.float32)
                   for _ in range(3))
        kq, vq, kd, vd = (P.to_tensor(np.zeros((B, H), np.float32))
                          for _ in range(4))
        run_blha(pack_qkv(q, k, v), kc, vc, [S] * B, [0] * B, [S] * B,
                 self.bt, self.bs, cache_k_quant_scales=kq,
                 cache_v_quant_scales=vq, cache_k_dequant_scales=kd,
                 cache_v_dequant_scales=vd, use_dynamic_cachekv_quant=True)
        expect = np.abs(k).max(axis=(2, 3)) / 127.0  # [B, H]
        np.testing.assert_allclose(np.asarray(kd.numpy()), expect, rtol=1e-4)
        assert (np.asarray(kq.numpy()) > 0).all()


class TestBlhaGetMaxLen:
    def test_max_lens(self):
        from paddle_tpu.incubate.nn.functional import blha_get_max_len

        enc = P.to_tensor(np.array([3, 9, 0], np.int32))
        dec = P.to_tensor(np.array([5, 0, 2], np.int32))
        me, md = blha_get_max_len(enc, dec, P.to_tensor(np.array([3])))
        assert int(np.asarray(me.numpy())[0]) == 9
        assert int(np.asarray(md.numpy())[0]) == 5


class TestTracedSeqLens:
    def test_traced_seq_lens_raises_clear_error(self):
        """ADVICE r5 low #3: the padded-query bucket is a HOST-side read of
        max(seq_lens_this_time); under jit tracing there is no concrete
        value, so the op must raise a clear error instead of crashing deep
        in numpy."""
        import jax
        import jax.numpy as jnp

        B, H, KV, D, bs = 2, 4, 4, 8, 8
        qkv = np.zeros((B, (H + 2 * KV) * D), np.float32)
        kc = np.zeros((4, KV, bs, D), np.float32)
        vc = np.zeros_like(kc)
        bt = np.zeros((B, 2), np.int32)
        cu = np.zeros((B + 1,), np.int32)
        zeros = np.zeros(B, np.int32)

        def f(lens):
            out = block_multihead_attention(
                P.to_tensor(qkv), P.to_tensor(kc), P.to_tensor(vc),
                P.to_tensor(zeros), P.to_tensor(zeros), lens,
                None, None, P.to_tensor(cu), P.to_tensor(cu),
                P.to_tensor(bt), block_size=bs)
            return out[0]._value

        with pytest.raises(ValueError, match="eagerly|ServingEngine"):
            jax.jit(f)(jnp.ones((B,), jnp.int32))


# ------------------------------------------------ the blocked pass's geometry
# ``blha_attention`` reads the context ``_CTX_BLOCK`` positions a pass, one-
# token rows in tiles of ``_ROW_TILE`` ordered by length, chunk rows one at a
# time. The reference below is the mathematics of the form it replaced: the
# WHOLE table gathered, every row padded to ``max_q_len`` queries, one
# softmax over the table's length, all in float32.
import functools                # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from paddle_tpu.ops import paged_attention as pa   # noqa: E402

CTX, TILE = pa._CTX_BLOCK, pa._ROW_TILE
G_BS, G_P, G_H, G_D = 8, 80, 4, 16       # a table of 640 positions, 64 columns a pass
G_L = G_BS * G_P


# traced ONCE a case: run op by op its forty-odd steps are forty-odd small
# programs compiled for every case's shapes (two thirds of this file's seconds)
@functools.partial(jax.jit, static_argnames=("S", "quant"))
def padded_reference(q, k, v, kc, vc, enc, dec, now, cu, bt, *, S, quant="none",
                     kd=None, vd=None, pre_k=None, pre_v=None, mask=None,
                     tgt_mask=None):
    """q [T, H, D], k / v [T, KV, D] (this step's, float32) against the pool
    AFTER the step's write -> [T, H, D] float32."""
    f32 = jnp.float32
    T, H, D = q.shape
    KV = k.shape[1]
    nb, B = kc.shape[0], bt.shape[0]
    L = bt.shape[1] * kc.shape[2]
    tok = jnp.arange(T)
    b_idx = jnp.clip(jnp.searchsorted(cu, tok, side="right") - 1, 0, B - 1)
    local = tok - cu[b_idx]
    abs_pos = dec[b_idx] + local
    valid = (tok < cu[-1]) & (local < now[b_idx])

    def gather(cache):
        ids = jnp.where((bt < 0) | (bt >= nb), nb, bt)
        g = cache.at[ids].get(mode="fill", fill_value=0)      # [B, P, KV, bs, D]
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(B, KV, L, D)

    k_all, v_all = gather(kc), gather(vc)
    if quant != "none":
        sk = kd[None, :, None, None] if quant == "static" else kd[:, :, None, None]
        sv = vd[None, :, None, None] if quant == "static" else vd[:, :, None, None]
        k_all = (k_all.astype(f32) - 128.0) * sk
        v_all = (v_all.astype(f32) - 128.0) * sv
        ob, op = jnp.where(valid, b_idx, B), jnp.where(valid, abs_pos, L)
        k_all = k_all.at[ob, :, op].set(k, mode="drop")
        v_all = v_all.at[ob, :, op].set(v, mode="drop")
    k_all, v_all = k_all.astype(f32), v_all.astype(f32)
    pre_len = 0
    if pre_k is not None:
        pre_len = pre_k.shape[2]
        k_all = jnp.concatenate([pre_k.astype(f32), k_all], axis=2)
        v_all = jnp.concatenate([pre_v.astype(f32), v_all], axis=2)
    Lf = pre_len + L
    bs_idx = jnp.where(valid, b_idx, B)
    lc_idx = jnp.where(valid & (local < S), local, S)
    q_pad = jnp.zeros((B, S, H, D), f32).at[bs_idx, lc_idx].set(q, mode="drop")
    g = H // KV
    logits = jnp.einsum("bskgd,bkld->bkgsl", q_pad.reshape(B, S, KV, g, D), k_all,
                        precision="highest") / (D ** 0.5)
    qpos = dec[:, None] + jnp.arange(S)[None, :]
    kpos = jnp.arange(Lf)[None, None, :] - pre_len
    logits = jnp.where((kpos <= qpos[:, :, None])[:, None, None], logits, -1e30)

    def add_mask(lg, m):
        m = jnp.broadcast_to(m.astype(f32), (B, H) + m.shape[2:])
        m = m[:, :, :S, :Lf]
        m = jnp.pad(m, ((0, 0), (0, 0), (0, S - m.shape[2]), (0, Lf - m.shape[3])))
        return lg + m.reshape(B, KV, g, S, Lf)

    if mask is not None:
        logits = jnp.where((enc > 0)[:, None, None, None, None],
                           add_mask(logits, mask), logits)
    if tgt_mask is not None:
        logits = jnp.where(((enc <= 0) & (now > 0))[:, None, None, None, None],
                           add_mask(logits, tgt_mask), logits)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgsl,bkld->bskgd", p, v_all, precision="highest")
    return out.reshape(B, S, H, D).at[bs_idx, lc_idx].get(mode="fill", fill_value=0)


# rows as (tokens already cached, tokens this step); (0, 0) is an empty slot
LAYOUTS = {
    # contexts of 1, bs - 1, bs, bs + 1, ctx_block - 1, ctx_block, ctx_block + 1
    # and the whole table; three tiles whose longest rows read the whole
    # table, one position past a ctx_block and one position; lengths out of
    # order, empty slots between
    "decode_edges": (1, [(CTX - 1, 1), (0, 1), (0, 0), (G_BS - 2, 1), (G_L - 1, 1),
                         (G_BS - 1, 1), (0, 0), (CTX - 2, 1), (G_BS, 1), (CTX, 1),
                         (300, 1), (1, 1), (CTX + 1, 1), (600, 1), (0, 0), (590, 1),
                         (580, 1), (570, 1), (560, 1), (550, 1), (540, 1)]),
    # a prompt from nothing; chunks that end on a block, on a ctx_block and on
    # the table's end; that start on a ctx_block, one position past it and at
    # one position; a one-token chunk tail
    "chunk_edges": (8, [(0, 8), (16, 8), (CTX - 8, 8), (0, 0), (CTX, 5), (CTX - 3, 6),
                        (G_L - 8, 8), (40, 1), (CTX + 1, 4), (1, 3)]),
    # what a mixed scan feeds: decode rows and chunk rows in one call
    "mixed": (8, [(200, 1), (CTX + 60, 1), (0, 8), (0, 0), (33, 1), (CTX - 4, 8),
                  (7, 1), (0, 0), (130, 3), (CTX, 1), (64, 1)]),
    # every row a chunk of the same width, as speculative verification feeds
    "drafts": (4, [(20, 4), (CTX + 1, 4), (0, 0), (CTX - 2, 4), (3, 2)]),
}


def _paired(pool, pack=2):
    """A pool ``[nb, KV, bs, D]`` with ``pack`` KV heads side by side in a row
    of lanes, ``[nb, KV / pack, bs, D * pack]`` (``pa.lane_packing``)."""
    nb, KV, bs, D = pool.shape
    return pool.reshape(nb, KV // pack, pack, bs, D).swapaxes(2, 3).reshape(
        nb, KV // pack, bs, D * pack)


def _a_head_a_row(pool, pack=2):
    """``_paired`` undone."""
    nb, rows, bs, lanes = pool.shape
    return pool.reshape(nb, rows, bs, pack, lanes // pack).swapaxes(2, 3).reshape(
        nb, rows * pack, bs, lanes // pack)


def _case(layout, group, dtype, quant, holes, pre, masks, paired=False, seed=0):
    """``paired``: heads of 64, two to a lane tile of the pool."""
    S, rows = LAYOUTS[layout]
    D = 64 if paired else G_D
    rng = np.random.RandomState(seed + len(layout))
    B, H, bs, P = len(rows), G_H, G_BS, G_P
    KV = H // group
    dec = np.array([r[0] for r in rows], np.int32)
    now = np.array([r[1] for r in rows], np.int32)
    enc = np.where(now > 1, now, 0).astype(np.int32)
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    T = int(cu[-1]) + 3                      # the packed buffer has a tail
    nb = B * P
    bt = rng.permutation(nb).reshape(B, P).astype(np.int32)
    need = -(-(dec + now) // bs)
    for b in range(B):
        bt[b, need[b]:] = -1                 # what a row does not hold
    if holes:                                # ... and holes inside what it does
        bt[0, 1] = -1
        bt[4, 0] = nb + 7
    qkv = rng.uniform(-1, 1, (T, (H + 2 * KV) * D)).astype(np.float32)
    kw = {}
    if quant == "none":
        kc = rng.uniform(-1, 1, (nb, KV, bs, D)).astype(np.float32)
        vc = rng.uniform(-1, 1, (nb, KV, bs, D)).astype(np.float32)
    else:
        kc = rng.randint(0, 256, (nb, KV, bs, D)).astype(np.uint8)
        vc = rng.randint(0, 256, (nb, KV, bs, D)).astype(np.uint8)
        shape = (KV,) if quant == "static" else (B, KV)
        for name in ("k", "v"):
            d = rng.uniform(0.5, 1.5, shape).astype(np.float32) / 127.0
            kw[f"cache_{name}_dequant_scales"] = jnp.asarray(d)
            kw[f"cache_{name}_quant_scales"] = jnp.asarray(1.0 / d)
    if pre:
        kw["pre_key_cache"] = rng.uniform(-1, 1, (B, KV, 5, D)).astype(np.float32)
        kw["pre_value_cache"] = rng.uniform(-1, 1, (B, KV, 5, D)).astype(np.float32)
    if masks:
        # an encoder mask over one head axis and a decoder mask over all
        # heads, neither as long as the table
        kw["mask"] = rng.uniform(-2, 0, (B, 1, S, CTX + 40)).astype(np.float32)
        kw["tgt_mask"] = rng.uniform(-2, 0, (B, H, 1, G_L - 17)).astype(np.float32)
    cast = (lambda a: jnp.asarray(a, dtype)) if quant == "none" else jnp.asarray
    if paired:
        kc, vc = _paired(kc), _paired(vc)
    args = (jnp.asarray(qkv, dtype), cast(kc), cast(vc), jnp.asarray(enc),
            jnp.asarray(dec), jnp.asarray(now), jnp.asarray(cu), jnp.asarray(bt))
    kw = {n: (jnp.asarray(a, dtype) if n.startswith("pre_") else jnp.asarray(a))
          for n, a in kw.items()}
    return args, kw, dict(H=H, KV=KV, D=D, bs=bs, S=S, T=T, total=int(cu[-1]))


def _p(layout, group=4, dtype="float32", quant="none", holes=False, pre=False,
       masks=False, paired=False):
    name = "-".join([layout, f"g{group}", dtype, quant]
                    + [n for n, on in (("holes", holes), ("pre", pre), ("masks", masks),
                                       ("paired", paired)) if on])
    return pytest.param(layout, group, dtype, quant, holes, pre, masks, paired, id=name)


GEOMETRY = (
    [_p(lay, group=g, dtype=dt) for lay in LAYOUTS for g in (1, 4)
     for dt in ("float32", "bfloat16")]
    + [_p(lay, quant=qm, dtype=dt) for lay in ("decode_edges", "mixed")
       for qm in ("static", "dynamic") for dt in ("float32", "bfloat16")]
    + [_p("mixed", holes=True), _p("decode_edges", holes=True, group=1),
       _p("chunk_edges", pre=True), _p("mixed", masks=True),
       _p("mixed", pre=True, masks=True, group=1),
       _p("decode_edges", pre=True, masks=True, dtype="bfloat16"),
       _p("drafts", masks=True, quant="dynamic"),
       _p("chunk_edges", pre=True, quant="static", dtype="bfloat16")]
    # heads of 64 two to a lane tile: four KV heads in two rows of the pool
    # (a group of 2 query heads a row), two in one (a group of 4)
    + [_p(lay, group=g, paired=True) for lay in LAYOUTS for g in (1, 2)]
    + [_p("mixed", group=2, dtype="bfloat16", paired=True),
       _p("mixed", group=1, holes=True, paired=True),
       _p("chunk_edges", group=2, pre=True, paired=True),
       _p("mixed", group=1, pre=True, masks=True, paired=True),
       _p("decode_edges", group=2, pre=True, masks=True, dtype="bfloat16", paired=True)])


@pytest.mark.parametrize("layout,group,dtype,quant,holes,pre,masks,paired", GEOMETRY)
def test_blocked_pass_matches_the_padded_form(layout, group, dtype, quant, holes, pre,
                                              masks, paired):
    args, kw, g = _case(layout, group, dtype, quant, holes, pre, masks, paired)
    qkv, _, _, enc, dec, now, cu, bt = args
    outs = pa.blha_attention(
        *args, num_heads=g["H"], kv_num_heads=g["KV"], head_dim=g["D"],
        block_size=g["bs"], max_q_len=g["S"], cache_quant=quant,
        compute_dtype=jnp.dtype(dtype), **kw)
    out, kc, vc = outs[0], outs[1], outs[2]
    assert out.dtype == jnp.dtype(dtype) and out.shape == (g["T"], g["H"] * g["D"])
    H, KV, D = g["H"], g["KV"], g["D"]
    if paired:
        assert kc.shape == args[1].shape == (args[1].shape[0], KV // 2, g["bs"], 128)
        kc, vc = _a_head_a_row(kc), _a_head_a_row(vc)
    f = qkv.astype(jnp.float32)
    want = padded_reference(
        f[:, :H * D].reshape(-1, H, D), f[:, H * D:(H + KV) * D].reshape(-1, KV, D),
        f[:, (H + KV) * D:].reshape(-1, KV, D), kc, vc, enc, dec, now, cu, bt,
        S=g["S"], quant=quant, kd=outs[5], vd=outs[6], pre_k=kw.get("pre_key_cache"),
        pre_v=kw.get("pre_value_cache"), mask=kw.get("mask"),
        tgt_mask=kw.get("tgt_mask"))
    tol = 2e-4 if dtype == "float32" else 5e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want).reshape(g["T"], H * D),
                               rtol=tol, atol=tol)
    assert not np.asarray(out[g["total"]:], np.float32).any()


# ------------------------------------------------ the one-token rows' kernel
# On the chip the rows that feed ONE token attend through
# ``ops/pallas/paged_decode.py`` when the call is one the kernel's conditions
# admit (``pa.decodes_in_kernel``). Here the platform is the CPU, so the tests
# steer ``on_tpu`` and run the kernel in interpret mode; the reference is the
# padded form above. Blocks of 16 in a table of 24, heads of 128: a pass is
# 8 blocks, 128 positions.
from paddle_tpu.ops.pallas import paged_chunk as pc    # noqa: E402
from paddle_tpu.ops.pallas import paged_decode as pd   # noqa: E402
from paddle_tpu.ops.pallas import paged_write as pw    # noqa: E402

K_BS, K_P, K_D, K_KV = 16, 24, 128, 2
K_PASS = pd.BLOCKS_PER_PASS * K_BS
K_L = K_BS * K_P

KERNEL_LAYOUTS = {
    # lengths (dec + 1) of 1, a block less one, a block, a block and one, a
    # pass, a pass and one, two passes and the whole table; a context that
    # ends inside a block; empty slots first, between and last; out of order
    "decode_edges": (1, [(0, 0), (K_PASS - 1, 1), (0, 1), (K_BS - 2, 1), (0, 0),
                         (K_L - 1, 1), (K_BS - 1, 1), (K_BS, 1), (K_PASS, 1),
                         (2 * K_PASS - 1, 1), (37, 1), (0, 0)]),
    # what a mixed scan feeds: only some rows feed one token; the chunk rows
    # (and a chunk's one-token tail, which is a one-token row) ride beside
    "mixed": (8, [(200, 1), (K_PASS + 60, 1), (0, 8), (0, 0), (33, 1),
                  (K_PASS - 4, 8), (7, 1), (130, 3), (K_PASS, 1), (40, 1)]),
    # no row feeds one token: the kernel makes no pass at all
    "chunks_only": (4, [(20, 4), (K_PASS + 1, 4), (0, 0), (3, 2)]),
}


def _kernel_case(layout, group, holes, seed=0, KV=K_KV, D=K_D):
    S, rows = KERNEL_LAYOUTS[layout]
    rng = np.random.RandomState(seed + len(layout))
    B, bs, P = len(rows), K_BS, K_P
    H = KV * group
    dec = np.array([r[0] for r in rows], np.int32)
    now = np.array([r[1] for r in rows], np.int32)
    enc = np.where(now > 1, now, 0).astype(np.int32)
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    T = int(cu[-1]) + 3
    nb = B * P
    bt = rng.permutation(nb).reshape(B, P).astype(np.int32)
    need = -(-(dec + now) // bs)
    for b in range(B):
        bt[b, need[b]:] = -1
    if holes:        # inside what a one-token row holds, but not its own token's block
        one = [b for b in range(B) if now[b] == 1 and need[b] >= 3]
        bt[one[0], 1] = -1
        bt[one[1], 0] = nb + 7
    bf16 = jnp.bfloat16
    qkv = jnp.asarray(rng.uniform(-1, 1, (T, (H + 2 * KV) * D)), bf16)
    kc = jnp.asarray(rng.uniform(-1, 1, (nb, KV, bs, D)), bf16)
    vc = jnp.asarray(rng.uniform(-1, 1, (nb, KV, bs, D)), bf16)
    args = (qkv, kc, vc, jnp.asarray(enc), jnp.asarray(dec), jnp.asarray(now),
            jnp.asarray(cu), jnp.asarray(bt))
    return args, dict(H=H, KV=KV, D=D, bs=bs, S=S, T=T, total=int(cu[-1]))


def _fresh_call(args, kw):
    """A fresh jit of the undecorated function: the platform is asked as it
    answers now, and no other test's trace is met or left behind."""
    statics = {n: v for n, v in kw.items() if not hasattr(v, "shape")}
    arrays = {n: v for n, v in kw.items() if hasattr(v, "shape")}
    return jax.jit(functools.partial(pa.blha_attention.__wrapped__, **statics))(
        *args, **arrays)


def _steer_onto_the_chip(monkeypatch, chunks=False):
    """``on_tpu`` answers yes and both kernels run in interpret mode; returns
    the list that grows by one with every ``paged_write`` call traced.
    ``chunks``: the chunk rows' kernel too (``paged_chunk``, interpreted); else
    they keep the XLA pass, as this file's cases compare them (that kernel's
    are tests/test_paged_chunk_kernel.py)."""
    calls = []

    def write(*a, **k):
        calls.append(a[0].shape)
        return pw.paged_write(*a, interpret=True, **k)

    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    monkeypatch.setattr(pa, "paged_decode",
                        functools.partial(pd.paged_decode, interpret=True))
    monkeypatch.setattr(pa, "paged_write", write)
    if chunks:
        monkeypatch.setattr(pa, "paged_chunk", functools.partial(pc.paged_chunk, interpret=True))
    else:
        monkeypatch.setattr(pa, "chunks_in_kernel", lambda *a, **k: False)
    return calls


@pytest.fixture
def on_chip(monkeypatch):
    """``blha_attention`` as the chip traces it: ``on_tpu`` answers yes, and
    the kernels it then calls (the one-token rows' attention, the cache
    write) run in interpret mode."""
    _steer_onto_the_chip(monkeypatch)
    return lambda *args, **kw: _fresh_call(args, kw)


def _kp(layout, group=4, holes=False):
    return pytest.param(layout, group, holes,
                        id=f"{layout}-g{group}" + ("-holes" if holes else ""))


@pytest.mark.parametrize("layout,group,holes", (
    [_kp(lay, g) for lay in KERNEL_LAYOUTS for g in (1, 4, 8)]
    + [_kp("decode_edges", holes=True), _kp("mixed", 8, holes=True)]))
def test_kernel_rows_match_the_padded_form(on_chip, layout, group, holes):
    args, g = _kernel_case(layout, group, holes)
    qkv, _, _, enc, dec, now, cu, bt = args
    seen = []
    inner = pa.paged_decode
    pa.paged_decode = lambda *a, **k: (jax.debug.callback(seen.append, a[3]),
                                       inner(*a, **k))[1]        # on_chip restores
    outs = on_chip(*args, num_heads=g["H"], kv_num_heads=g["KV"], head_dim=g["D"],
                   block_size=g["bs"], max_q_len=g["S"], compute_dtype=jnp.bfloat16)
    # the kernel was given the one-token rows' lengths and nothing of the others
    jax.effects_barrier()
    assert len(seen) == 1
    np.testing.assert_array_equal(np.asarray(seen[0]),
                                  np.where(np.asarray(now) == 1, np.asarray(dec) + 1, 0))
    out, kc, vc = outs[0], outs[1], outs[2]
    H, KV, D = g["H"], g["KV"], g["D"]
    f = qkv.astype(jnp.float32)
    want = padded_reference(
        f[:, :H * D].reshape(-1, H, D), f[:, H * D:(H + KV) * D].reshape(-1, KV, D),
        f[:, (H + KV) * D:].reshape(-1, KV, D), kc, vc, enc, dec, now, cu, bt, S=g["S"])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want).reshape(g["T"], H * D),
                               rtol=5e-3, atol=5e-3)
    assert not np.asarray(out[g["total"]:], np.float32).any()


@pytest.mark.parametrize("per", [1, 3, 8], ids=lambda n: f"pass{n}")
def test_kernel_alone_holds_the_probabilities_to_two_terms(per):
    """The kernel's own float32 output against float64 arithmetic on the same
    bf16 values: scores are exact products, and ``p_hi + p_lo`` keeps 16 bits
    of a probability (a single bf16 term would err by 2e-3 here). A pass of
    one block, of three (the table is not whole passes) and of eight."""
    rng = np.random.RandomState(per)
    B, KV, g, D, bs, P, nb = 5, 2, 4, K_D, K_BS, 7, 40
    q = jnp.asarray(rng.uniform(-1, 1, (B, KV, g, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.uniform(-1, 1, (nb, KV, bs, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.uniform(-1, 1, (nb, KV, bs, D)), jnp.bfloat16)
    lens = np.array([0, 70, 1, P * bs, 33], np.int32)
    bt = rng.permutation(nb)[:B * P].reshape(B, P).astype(np.int32)
    bt[1, 2] = -1
    out = np.asarray(pd.paged_decode(q, kc, vc, jnp.asarray(lens), jnp.asarray(bt),
                                     scale=D ** -0.5, blocks_per_pass=per,
                                     interpret=True))
    assert out.shape == (B, KV, g, D) and out.dtype == np.float32
    for b in range(B):
        n = lens[b]
        if n == 0:
            assert not out[b].any()
            continue
        def dense(c):
            blocks = [np.asarray(c[i], np.float64) if i >= 0 else np.zeros((KV, bs, D))
                      for i in bt[b]]
            return np.stack(blocks, 1).reshape(KV, P * bs, D)[:, :n]
        s = np.einsum("kgd,kld->kgl", np.asarray(q[b], np.float64), dense(kc)) * D ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("kgl,kld->kgd", p / p.sum(-1, keepdims=True), dense(vc))
        np.testing.assert_allclose(out[b], want, rtol=0, atol=2e-5)


def _lowered(args, g, scopes=False, **kw):
    f = functools.partial(pa.blha_attention.__wrapped__, num_heads=g["H"],
                          kv_num_heads=g["KV"], head_dim=g["D"], block_size=g["bs"],
                          max_q_len=g["S"], **{n: v for n, v in kw.items()
                                               if not hasattr(v, "shape")})
    arrays = {n: v for n, v in kw.items() if hasattr(v, "shape")}
    return jax.jit(f).lower(*args, **arrays).as_text(debug_info=scopes)


def _outside_cases():
    def admitted():
        return _kernel_case("mixed", 4, False)

    def float32_cache():
        (qkv, kc, vc, *rest), g = admitted()
        return (qkv, kc.astype(jnp.float32), vc.astype(jnp.float32), *rest), g, {}

    def float32_queries():
        args, g = admitted()
        return args, g, {"compute_dtype": jnp.float32}

    def int8_cache():
        (qkv, kc, vc, *rest), g = admitted()
        scales = {f"cache_{n}_{kind}_scales": jnp.ones((g["KV"],), jnp.float32)
                  for n in "kv" for kind in ("quant", "dequant")}
        return ((qkv, kc.astype(jnp.uint8), vc.astype(jnp.uint8), *rest), g,
                dict(scales, cache_quant="static"))

    def with_mask():
        args, g = admitted()
        return args, g, {"tgt_mask": jnp.zeros((len(args[4]), 1, 1, K_L), jnp.float32)}

    def encoder_mask():
        args, g = admitted()
        return args, g, {"mask": jnp.zeros((len(args[4]), 1, g["S"], K_L), jnp.float32)}

    def pre_cache():
        args, g = admitted()
        pre = jnp.zeros((len(args[4]), g["KV"], 5, g["D"]), jnp.bfloat16)
        return args, g, {"pre_key_cache": pre, "pre_value_cache": pre}

    def odd_narrow_heads():  # three heads of 64: no two share a lane tile
        return *_kernel_case("mixed", 2, False, KV=3, D=64), {}

    def unpaired_narrow_heads():     # the pool says how it lies: a head a row of 64
        return *_kernel_case("mixed", 2, False, KV=4, D=64), {}

    return [float32_cache, float32_queries, int8_cache, with_mask, encoder_mask,
            pre_cache, odd_narrow_heads, unpaired_narrow_heads]


# of those, the calls whose WRITE still goes through ``paged_write``: masks,
# pre-caches and the queries' type are the attention's, the pool is bf16
_WRITTEN_BY_ROW = {"float32_queries", "with_mask", "encoder_mask", "pre_cache"}


@pytest.mark.parametrize("make", _outside_cases(), ids=lambda f: f.__name__)
def test_a_call_outside_the_kernels_conditions_takes_the_xla_pass(monkeypatch, make):
    """Steered onto the chip or not, the attention of such a call is the
    blocked XLA pass, with no ``paged_decode`` in it. Where the pool is not
    one the write's kernel admits either (float32, int8, a head a row of
    half a lane tile) both lower to one and the same text with no custom call at all (at PR 29
    that text was the parent commit's byte for byte, compared by hand); the
    others differ by the ``paged_write`` call alone."""
    args, g, kw = make()
    kw.setdefault("compute_dtype", jnp.bfloat16)
    here = _lowered(args, g, **kw)
    assert "custom_call" not in here
    assert "kv_gather" in _lowered(args, g, scopes=True, **kw)
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    if make.__name__ not in _WRITTEN_BY_ROW:
        assert _lowered(args, g, **kw) == here
        return
    f = functools.partial(pa.blha_attention.__wrapped__, num_heads=g["H"],
                          kv_num_heads=g["KV"], head_dim=g["D"], block_size=g["bs"],
                          max_q_len=g["S"], **{n: v for n, v in kw.items()
                                               if not hasattr(v, "shape")})
    there = jax.jit(f).trace(*args, **{n: v for n, v in kw.items() if hasattr(v, "shape")}
                             ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "paged_write" in there and "paged_decode" not in there
    assert "kv_gather" in there and "kv_write/scatter" not in there


def test_the_platform_chooses_between_the_kernel_and_the_xla_pass(monkeypatch):
    """The same admitted call: on the CPU the XLA pass; where ``on_tpu``
    answers yes, lowered for the TPU, the ``paged_decode`` custom call, and
    at ``max_q_len`` 1 no gather at all."""
    args, g = _kernel_case("decode_edges", 4, False)
    kw = dict(compute_dtype=jnp.bfloat16)
    here = _lowered(args, g, scopes=True, **kw)
    assert "tpu_custom_call" not in here and "kv_gather" in here
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    f = functools.partial(pa.blha_attention.__wrapped__, num_heads=g["H"],
                          kv_num_heads=g["KV"], head_dim=g["D"], block_size=g["bs"],
                          max_q_len=g["S"], **kw)
    there = jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)
    assert "tpu_custom_call" in there and "paged_decode" in there
    assert "kv_gather" not in there
    # and the cache write: the scatter here, the ``paged_write`` call there
    assert "kv_write/scatter" in here and "paged_write" not in here
    assert "paged_write" in there and "kv_write/scatter" not in there


def test_attention_positions_by_hand_on_both_paths():
    """Blocks of 16 in a table of 64: the XLA pass reads 512 positions a pass
    for the 8 rows of a tile and this step's token from registers; the kernel
    reads a one-token row's own context, its token with it, to a block."""
    def count(rows, kernel):
        dec, now = (jnp.asarray(x, jnp.int32) for x in zip(*rows))
        return tuple(int(n) for n in pa.attention_positions(
            dec, now, block_size=16, blocks_per_seq=64, kernel=kernel))

    rows = [(600, 1), (10, 1), (0, 0), (15, 1), (16, 1), (520, 4)]
    live = 601 + 11 + 16 + 17 + 524
    # one tile of the four one-token rows, the longest of 600: two passes for
    # eight rows, and four tokens from registers; the chunk row its own two
    assert count(rows, False) == (live, 2 * 512 * 8 + 4 + 2 * 512 + 4, 0)
    # lengths 601, 11, 16, 17 to blocks of 16: 608 + 16 + 16 + 32; the chunk
    # row is the XLA pass's on both paths
    assert count(rows, True) == (live, 608 + 16 + 16 + 32 + 2 * 512 + 4, 4)
    assert count([(0, 0), (0, 7)], True) == (7, 7, 0)


def test_decodes_in_kernel_asks_the_call_and_the_platform_only(monkeypatch):
    ask = functools.partial(pa.decodes_in_kernel, head_dim=128, block_size=64,
                            rows=32, blocks_per_seq=40)
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert not ask(bf16, bf16)                       # the CPU
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    assert ask(bf16, bf16)
    assert not ask(bf16, bf16, plain=False)
    assert not ask(f32, bf16) and not ask(bf16, f32) and not ask(bf16, jnp.uint8)
    assert not ask(jnp.float16, jnp.float16)         # Mosaic refuses float16 tiles
    for odd in (dict(head_dim=64), dict(head_dim=192), dict(block_size=8),
                dict(rows=4096, blocks_per_seq=64)):
        assert not pa.decodes_in_kernel(bf16, bf16, **{
            **dict(head_dim=128, block_size=64, rows=32, blocks_per_seq=40), **odd})
    # ``head_dim`` is the width of the POOL's rows: 8 heads of 64 lie two to a
    # lane tile and are asked as 4 of 128; 7 lie one a row of 64, and are refused
    for kv, lanes in ((8, 128), (7, 64)):
        _, block = pa.lane_packing(kv, 64)
        assert block(64)[2] == lanes and ask(bf16, bf16, head_dim=lanes) == (lanes == 128)


# ------------------------------------------------- the row-wise cache write
# On the chip a call with an unquantised bf16 pool writes this step's keys and
# values through ``ops/pallas/paged_write.py`` (``pa.writes_in_kernel``): the
# pieces of the blocks a row holds, brought, selected into and put back. The
# reference is the scatter, which every other call keeps: the POOLS must come
# out bit for bit the same. Blocks of 64 in a table of 4, so a piece is a
# quarter of a block; heads of 128.
W_BS, W_P, W_KV, W_D = 64, 4, 2, 128

WRITE_LAYOUTS = {
    # (max_q_len, [(dec, now)]); T is the rows' tokens and a dead tail
    "decode_only": (1, [(0, 1), (15, 1), (16, 1), (63, 1), (64, 1), (200, 1), (255, 1)]),
    "chunk_inside_a_block": (64, [(64, 64), (130, 1), (0, 40)]),
    "chunk_across_a_blocks_end": (64, [(40, 64), (7, 1), (100, 64), (63, 2)]),
    "a_prompts_tail": (64, [(128, 5), (64, 1), (70, 17), (191, 1)]),
    "idle_rows_between": (64, [(0, 0), (33, 1), (0, 0), (0, 0), (64, 30), (9, 0), (77, 1),
                               (0, 0)]),
    "a_run_longer_than_a_block": (200, [(3, 200), (50, 1), (0, 130)]),
    "nothing_live": (64, [(0, 0), (12, 0)]),
}


def _write_case(layout, *, holes=False, stacked=None, dtype=jnp.bfloat16, seed=0):
    S, rows = WRITE_LAYOUTS[layout]
    rng = np.random.RandomState(seed + len(layout))
    B, KV, D, bs, P, H = len(rows), W_KV, W_D, W_BS, W_P, 2 * W_KV
    dec = np.array([r[0] for r in rows], np.int32)
    now = np.array([r[1] for r in rows], np.int32)
    enc = np.where(now > 1, now, 0).astype(np.int32)
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    T = int(cu[-1]) + 5
    nb = B * P + 3
    bt = rng.permutation(nb)[:B * P].reshape(B, P).astype(np.int32)
    if holes:       # under the first two live rows' own tokens
        live = [b for b in range(B) if now[b] > 0]
        bt[live[0], dec[live[0]] // bs] = -1
        bt[live[1], dec[live[1]] // bs] = nb + 7
    shape = (nb, KV, bs, D) if stacked is None else (3, nb, KV, bs, D)
    qkv = jnp.asarray(rng.uniform(-1, 1, (T, (H + 2 * KV) * D)), jnp.bfloat16)
    kc = jnp.asarray(rng.uniform(-1, 1, shape), dtype)
    vc = jnp.asarray(rng.uniform(-1, 1, shape), dtype)
    args = (qkv, kc, vc, jnp.asarray(enc), jnp.asarray(dec), jnp.asarray(now),
            jnp.asarray(cu), jnp.asarray(bt))
    kw = dict(num_heads=H, kv_num_heads=KV, head_dim=D, block_size=bs, max_q_len=S,
              compute_dtype=jnp.bfloat16)
    if stacked is not None:
        kw["layer"] = jnp.int32(stacked)
    return args, kw


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32, 1: jnp.uint8}[x.dtype.itemsize]))


def _wp(layout, **kw):
    return pytest.param(layout, kw, id="-".join(
        [layout] + [f"{k}{'' if v is True else v}" for k, v in kw.items()]))


@pytest.mark.parametrize("layout,how", (
    [_wp(lay) for lay in WRITE_LAYOUTS]
    + [_wp("decode_only", holes=True), _wp("chunk_across_a_blocks_end", holes=True)]
    + [_wp("chunk_across_a_blocks_end", stacked=l) for l in (0, 1, 2)]
    + [_wp("decode_only", stacked=2), _wp("idle_rows_between", stacked=1, holes=True)]))
def test_the_row_wise_write_leaves_the_pools_the_scatter_leaves(monkeypatch, layout, how):
    """The same call as the CPU runs it (the scatter, the XLA pass) and as the
    chip traces it (``paged_write`` and ``paged_decode``, in interpret mode):
    the two pools bit for bit, a stacked pool's other layers untouched, a
    block the table does not name not written and no other touched, and the
    attention's output, which on the chip reads a one-token row's own token
    back out of the pool."""
    args, kw = _write_case(layout, **how)
    want = _fresh_call(args, kw)
    calls = _steer_onto_the_chip(monkeypatch)
    got = _fresh_call(args, kw)
    assert len(calls) == 1
    dec, now, cu = (np.asarray(a) for a in (args[4], args[5], args[6]))
    for pool, mine, theirs, before in zip("kv", got[1:3], want[1:3], args[1:3]):
        assert mine.shape == before.shape and mine.dtype == before.dtype
        np.testing.assert_array_equal(_bits(mine), _bits(theirs), err_msg=pool)
        if "stacked" in how:
            others = [l for l in range(3) if l != how["stacked"]]
            np.testing.assert_array_equal(_bits(mine)[others], _bits(before)[others])
        assert (_bits(mine) != _bits(before)).any() == bool(now.sum())
    # a row whose own token found no block reads zeros for it from the pool
    # on the chip and the fresh token from registers here: leave those out
    sound = np.ones(args[0].shape[0], bool)
    if how.get("holes"):
        for b in [b for b in range(len(now)) if now[b] > 0][:2]:
            sound[cu[b]:cu[b] + now[b]] = False
    np.testing.assert_allclose(np.asarray(got[0], np.float32)[sound],
                               np.asarray(want[0], np.float32)[sound],
                               rtol=1e-2, atol=1e-2)


def _refused_cases():
    def the_cpu():
        return _write_case("chunk_across_a_blocks_end"), False

    def float32_pool():
        return _write_case("chunk_across_a_blocks_end", dtype=jnp.float32), True

    def int8_pool():
        args, kw = _write_case("chunk_across_a_blocks_end", dtype=jnp.uint8)
        scales = {f"cache_{n}_{kind}_scales": jnp.ones((W_KV,), jnp.float32)
                  for n in "kv" for kind in ("quant", "dequant")}
        return (args, dict(kw, cache_quant="static", **scales)), True

    def blocks_of_half_a_piece():
        args, kw = _write_case("decode_only")
        qkv, kc, vc, *rest = args
        cut = lambda c: c.reshape(-1, W_KV, 8, W_D)               # noqa: E731
        return ((qkv, cut(kc), cut(vc), *rest), dict(kw, block_size=8)), True

    return [the_cpu, float32_pool, int8_pool, blocks_of_half_a_piece]


@pytest.mark.parametrize("make", _refused_cases(), ids=lambda f: f.__name__)
def test_a_call_the_writes_conditions_refuse_takes_the_scatter(monkeypatch, make):
    """The CPU, a float32 pool, an int8 cache, blocks that are no whole
    pieces: the scatter, whatever the platform says, and the pools it leaves
    are the unsteered call's."""
    (args, kw), steered = make()
    want = _fresh_call(args, kw)
    calls = _steer_onto_the_chip(monkeypatch) if steered else []
    got = _fresh_call(args, kw)
    assert not calls
    for mine, theirs in zip(got[1:3], want[1:3]):
        np.testing.assert_array_equal(_bits(mine), _bits(theirs))
    statics = {n: v for n, v in kw.items() if not hasattr(v, "shape")}
    text = jax.jit(functools.partial(pa.blha_attention.__wrapped__, **statics)).lower(
        *args, **{n: v for n, v in kw.items() if hasattr(v, "shape")}).as_text(
        debug_info=True)
    assert "kv_write/scatter" in text and "paged_write" not in text


def test_writes_in_kernel_asks_the_pool_and_the_platform_only(monkeypatch):
    usual = dict(head_dim=128, block_size=64, rows=32, blocks_per_seq=40, tokens=256,
                 kv_heads=8)
    ask = functools.partial(pa.writes_in_kernel, **usual)
    assert not ask(jnp.bfloat16)                     # the CPU
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    assert ask(jnp.bfloat16)
    assert not ask(jnp.float32) and not ask(jnp.uint8) and not ask(jnp.float16)
    # 8,192 tokens x 8 heads of keys and of values are 34 MB: no VMEM holds them
    for odd in (dict(head_dim=64), dict(head_dim=192), dict(block_size=8),
                dict(block_size=24), dict(rows=4096, blocks_per_seq=64),
                dict(tokens=8192)):
        assert not pa.writes_in_kernel(jnp.bfloat16, **{**usual, **odd})
    assert ask(jnp.bfloat16, tokens=1024)
    # asked with the pool's rows, as ``decodes_in_kernel`` is: heads of 64 two
    # to a lane tile are ``kv_heads`` 4 of ``head_dim`` 128, the same values
    (rows, _, lanes) = pa.lane_packing(8, 64)[1](64)
    assert (rows, lanes) == (4, 128) and ask(jnp.bfloat16, kv_heads=rows, head_dim=lanes)


def test_cache_write_counts_by_hand():
    """Tokens: a row's ``now``, cut at ``cu[-1]``. Pieces of 16 positions: a
    token one; 64 tokens from a block's start four, from position 40 five
    (40-47, 48-63, ..., 96-103); 5 tokens from 14 two; nothing for a row that
    feeds nothing, and nothing at all where the scatter runs."""
    def count(rows, kernel, total=None):
        dec, now = (np.asarray(x, np.int32) for x in zip(*rows))
        cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
        if total is not None:
            cu[-1] = total
        return tuple(int(n) for n in pa.cache_write_counts(
            jnp.asarray(dec), jnp.asarray(now), jnp.asarray(cu), kernel=kernel))

    rows = [(600, 1), (0, 0), (64, 64), (40, 64), (14, 5), (15, 1), (9, 0)]
    assert count(rows, True) == (135, 1 + 4 + 5 + 2 + 1)
    assert count(rows, False) == (135, 0)
    assert count([(0, 0), (5, 0)], True) == (0, 0)
    # the buffer ends inside the last row's run: 3 of its 5 tokens are live
    assert count([(0, 16), (14, 5)], True, total=19) == (19, 1 + 2)


# ------------------------------------- heads of 64, two to a lane tile
# A pool whose block is ``pa.lane_packing``'s holds two KV heads of 64 side by
# side in a 128-lane row, ``[nb, KV / 2, bs, 128]``, and ``blha_attention``
# reads the form off the pool it is handed: the packed buffer's keys are
# already rows of such a pool, and a pair of KV heads is ONE head of 128 to a
# group of twice the query heads whose queries are zero outside their own
# half. So on the chip both kernels take it as it lies; everywhere else the
# scatter and the XLA pass do (``paired`` in GEOMETRY above).
def test_lane_packing_by_hand():
    def ask(kv, d, bs=16):
        pack, block = pa.lane_packing(kv, d)
        return pack, block(bs)

    assert ask(8, 64) == (2, (4, 16, 128)) and ask(2, 64) == (2, (1, 16, 128))
    assert ask(8, 32) == (4, (2, 16, 128))
    # heads of whole lane tiles, an odd head out, a width that fills no tile
    for kv, d in ((8, 128), (8, 256), (3, 64), (1, 64), (6, 32), (8, 96), (8, 48)):
        assert ask(kv, d) == (1, (kv, 16, d))


@pytest.mark.parametrize("layout,kv,holes", [
    pytest.param(lay, kv, holes, id=f"{lay}-kv{kv}" + ("-holes" if holes else ""))
    for lay, kv, holes in (("mixed", 4, False), ("mixed", 2, True), ("decode_edges", 4, True),
                           ("decode_edges", 2, False), ("chunks_only", 4, False))])
def test_heads_of_64_take_both_kernels_two_to_a_lane_tile(monkeypatch, layout, kv, holes):
    """Eight query heads over four (and two) KV heads of 64: the call over a
    pool a head a row (the scatter, the XLA pass), over the paired pool on
    the CPU (the same two over rows of 128) and over the paired pool as the
    chip traces it (``paged_write`` given ``[T, KV / 2, 128]``, ``paged_decode``
    a group of ``2 g`` queries of 128, in interpret mode). The pools come out
    byte for byte the first call's, paired; the three outputs are the float32
    reference's."""
    H, D = 8, 64
    args, g = _kernel_case(layout, H // kv, holes, KV=kv, D=D)
    qkv, kc, vc, enc, dec, now, cu, bt = args
    kw = dict(num_heads=H, kv_num_heads=kv, head_dim=D, block_size=g["bs"],
              max_q_len=g["S"], compute_dtype=jnp.bfloat16)
    a_row = _fresh_call(args, kw)
    paired = (qkv, _paired(kc), _paired(vc), *args[3:])
    here = _fresh_call(paired, kw)
    writes, decodes = _steer_onto_the_chip(monkeypatch), []
    inner = pa.paged_decode
    monkeypatch.setattr(pa, "paged_decode", lambda *a, **k: (
        decodes.append(a[0].shape), inner(*a, **k))[1])
    there = _fresh_call(paired, kw)
    assert writes == [(g["T"], kv // 2, 128)]
    assert decodes == [(len(dec), kv // 2, 2 * (H // kv), 128)]
    for got in (here, there):
        for mine, theirs in zip(got[1:3], a_row[1:3]):
            np.testing.assert_array_equal(_bits(mine), _bits(_paired(theirs)))
    f = qkv.astype(jnp.float32)
    want = np.asarray(padded_reference(
        f[:, :H * D].reshape(-1, H, D), f[:, H * D:(H + kv) * D].reshape(-1, kv, D),
        f[:, (H + kv) * D:].reshape(-1, kv, D), a_row[1], a_row[2], enc, dec, now, cu, bt,
        S=g["S"])).reshape(g["T"], H * D)
    for got in (a_row, here, there):
        np.testing.assert_allclose(np.asarray(got[0], np.float32), want, rtol=5e-3, atol=5e-3)
        assert not np.asarray(got[0][g["total"]:], np.float32).any()


def test_a_paired_pool_is_not_quantised():
    """The int8 scales are a KV head's, and a row of a paired pool holds two:
    such a pool is refused by name (no model declares one: a spec that pairs
    is not ``quantizable``)."""
    args, kw, g = _case("mixed", 1, "float32", "static", False, False, False)
    qkv, kc, vc, *rest = args
    wide = jnp.zeros((kc.shape[0], g["KV"] // 2, g["bs"], 128), jnp.uint8)
    qkv = jnp.zeros((g["T"], (g["H"] + 2 * g["KV"]) * 64), jnp.float32)
    with pytest.raises(ValueError, match="packs 2 heads of 64"):
        pa.blha_attention(qkv, wide, wide, *rest, num_heads=g["H"], kv_num_heads=g["KV"],
                          head_dim=64, block_size=g["bs"], max_q_len=g["S"],
                          cache_quant="static", **kw)


# ------------------------------------------------------- a sliding window
# ``blha_attention(window=)``: the blocked XLA pass, the ``paged_decode`` kernel in
# interpret mode (groups of 7 query heads a key/value head, a fetch list that
# starts at the window's first block), the table's holes behind the window, and
# what ``paged_counts`` says the window spared.  The reference is a dense
# attention under an explicit mask over what the pools hold after the call.
def _window_case(rows, *, bs, P, KV, group, D, dtype, seed=0, behind=None, window=None):
    """rows [(dec, now)] -> blha's arguments over random pools and a shuffled
    table; ``behind``: what the entries wholly behind ``window`` read as (None:
    the blocks they always named; -1: no block, as an engine leaves them)."""
    rng = np.random.RandomState(seed)
    B, H = len(rows), KV * group
    dec = np.array([r[0] for r in rows], np.int32)
    now = np.array([r[1] for r in rows], np.int32)
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    T = int(cu[-1]) + 2
    nb = B * P
    bt = rng.permutation(nb).reshape(B, P).astype(np.int32)
    for b in range(B):
        bt[b, -(-(dec[b] + now[b]) // bs):] = -1
        if behind is not None:
            bt[b, :max(dec[b] - window + 1, 0) // bs] = behind
    qkv = jnp.asarray(rng.uniform(-1, 1, (T, (H + 2 * KV) * D)), dtype)
    kc = jnp.asarray(rng.uniform(-1, 1, (nb, KV, bs, D)), dtype)
    vc = jnp.asarray(rng.uniform(-1, 1, (nb, KV, bs, D)), dtype)
    args = (qkv, kc, vc, jnp.asarray(np.where(now > 1, now, 0).astype(np.int32)),
            jnp.asarray(dec), jnp.asarray(now), jnp.asarray(cu), jnp.asarray(bt))
    return args, dict(num_heads=H, kv_num_heads=KV, head_dim=D, block_size=bs,
                      max_q_len=int(max(now.max(), 1)), compute_dtype=dtype)


def _window_dense(args, kw, kc, vc, window):
    """Every live token's attention from the pools AFTER the call (they hold
    this step's keys and values too), under an explicit causal-and-window mask."""
    qkv, _, _, _, dec, now, cu, bt = (np.asarray(a, np.float32) if i < 3 else np.asarray(a)
                                      for i, a in enumerate(args))
    H, KV, D, bs = kw["num_heads"], kw["kv_num_heads"], kw["head_dim"], kw["block_size"]
    kc, vc = np.asarray(kc, np.float32), np.asarray(vc, np.float32)
    out = np.zeros((qkv.shape[0], H, D), np.float32)
    for b in range(len(dec)):
        n = int(dec[b] + now[b])
        lo = 0 if window is None else max(int(dec[b]) - window + 1, 0)
        pos = np.arange(lo, n)                      # nothing behind ``lo`` is ever attended
        blk, slot = bt[b, pos // bs], pos % bs
        k, v = kc[blk, :, slot], vc[blk, :, slot]   # [L, KV, D]
        for i in range(int(now[b])):
            t = int(cu[b]) + i
            q = qkv[t, :H * D].reshape(KV, H // KV, D)
            at = int(dec[b]) + i
            ok = (pos <= at) & ((pos > at - window) if window is not None else True)
            s = np.einsum("kgd,lkd->kgl", q, k) * D ** -0.5
            s = np.where(ok[None, None], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[t] = np.einsum("kgl,lkd->kgd", p, v).reshape(H, D)
    return out.reshape(qkv.shape[0], H * D)


# window 24 over blocks of 8 in a table of 20 (a pass is the whole table on the
# CPU): one-token rows short of, at and far past the window; chunk rows whose
# queries each have their own first key, one longer than the window itself
XLA_ROWS = [(0, 1), (23, 1), (24, 1), (100, 1), (0, 0), (130, 1), (0, 30), (50, 8),
            (120, 5), (3, 1), (17, 12), (64, 1)]


@pytest.mark.parametrize("behind", [None, -1], ids=["blocks_kept", "blocks_given_back"])
def test_the_blocked_pass_attends_the_window_alone(behind):
    """Each query at t attends t - 23 .. t, whether the table still names the
    blocks behind the window or reads -1 there (what an engine leaves after it
    has given them back): what lies behind is never gathered."""
    W = 24
    args, kw = _window_case(XLA_ROWS, bs=8, P=20, KV=2, group=3, D=16, dtype=jnp.float32,
                     behind=behind, window=W)
    out, kc, vc, *_ = pa.blha_attention(*args, window=W, **kw)
    full, kc0, vc0, *_ = pa.blha_attention(*args, **kw)
    want = _window_dense(args, kw, kc, vc, W)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    # the window is the difference: a row past it reads other values without it
    if behind is None:
        np.testing.assert_allclose(np.asarray(full), _window_dense(args, kw, kc0, vc0, None),
                                   rtol=2e-5, atol=2e-5)
        assert np.abs(np.asarray(full) - np.asarray(out)).max() > 1e-2
    np.testing.assert_array_equal(_bits(kc), _bits(kc0))      # the write knows no window


def test_a_large_context_block_starts_its_walk_at_the_windows_pass(monkeypatch):
    """Passes of 32 positions over contexts of hundreds: the walk starts at the
    pass that holds the first key, and ``attention_positions`` says so."""
    monkeypatch.setattr(pa, "_CTX_BLOCK", 32)
    W = 24
    rows = [(300, 1), (200, 1), (10, 1), (290, 8), (0, 0), (150, 1), (95, 1), (31, 1), (33, 4)]
    args, kw = _window_case(rows, bs=8, P=48, KV=2, group=2, D=16, dtype=jnp.float32,
                     behind=-1, window=W)
    out, kc, vc, *_ = _fresh_call(args, dict(kw, window=W))
    np.testing.assert_allclose(np.asarray(out), _window_dense(args, kw, kc, vc, W),
                               rtol=2e-5, atol=2e-5)
    dec, now = args[4], args[5]
    live, read, _ = pa.attention_positions(dec, now, block_size=8, blocks_per_seq=48, window=W)
    _, read_all, _ = pa.attention_positions(dec, now, block_size=8, blocks_per_seq=48)
    assert int(live) == sum(d + n for d, n in rows if n)
    # by hand: the chunk rows walk from the pass of dec - 23; the one tile of
    # eight one-token rows (sorted by length) from the pass its shortest starts at
    chunk = ((290 + 31) // 32 - (290 - 23) // 32) * 32 + 8 + ((33 + 31) // 32 - 0) * 32 + 4
    ones = ((300 + 31) // 32 - 0) * 8 * 32 + 6
    assert int(read) == chunk + ones < int(read_all)


# the kernel: blocks of 16 in a table of 24, heads of 128, a pass of 8 blocks;
# a window of 100 positions = 7 blocks: rows short of it, at it, past it by
# less and by more than a pass, and to the table's end
K_ROWS = [(0, 1), (99, 1), (100, 1), (0, 0), (229, 1), (383, 1), (37, 1), (150, 8), (131, 1)]


@pytest.mark.parametrize("group", [7, 4], ids=lambda g: f"g{g}")
def test_the_kernel_takes_groups_of_seven_and_a_windows_first_block(monkeypatch, group):
    """``paged_decode`` in interpret mode, 7 query heads a key/value head (padded
    to a sublane tile of 8 inside the kernel, never in the pool): a row's fetch
    list starts at the block that holds ``length - window`` and the table names
    NO block behind it; the result is the dense reference's, and both pools come
    out bit for bit as the scatter leaves them."""
    W = 100
    args, kw = _window_case(K_ROWS, bs=16, P=24, KV=2, group=group, D=128, dtype=jnp.bfloat16,
                     behind=-1, window=W)
    scatter = pa.blha_attention(*args, window=W, **kw)          # the CPU: XLA pass, scatter
    writes = _steer_onto_the_chip(monkeypatch)
    seen = []
    inner = pa.paged_decode
    monkeypatch.setattr(pa, "paged_decode", lambda *a, **k: (seen.append(k), inner(*a, **k))[1])
    out, kc, vc, *_ = _fresh_call(args, dict(kw, window=W))
    assert len(writes) == 1 and [k["window"] for k in seen] == [W]
    want = _window_dense(args, kw, kc, vc, W)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(scatter[0], np.float32),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_array_equal(_bits(kc), _bits(scatter[1]))
    np.testing.assert_array_equal(_bits(vc), _bits(scatter[2]))
    counts = pa.paged_counts(jnp.bfloat16, args[1], args[4], args[5], args[6], args[7],
                             tokens=args[0].shape[0], heads=kw["num_heads"], max_q_len=kw["max_q_len"],
                             window=W)
    # a one-token row brings the blocks from its window's first to its own: 16 k
    # under a window of 4,096 is 65 blocks of 251 by the same arithmetic
    ones = [d for d, n in K_ROWS if n == 1]
    assert int(counts["attn_rows_kernel"]) == len(ones)
    by_hand = sum((d + 16) // 16 - max(d - W + 1, 0) // 16 for d in ones) * 16
    # the chunk row rides the XLA pass, whose pass is the whole table of 24 x 16 here
    assert int(counts["attn_positions_read"]) == by_hand + 24 * 16 + 8
    assert (16000 + 64) // 64 - (16000 - 4095) // 64 == 65


def test_the_kernel_without_a_window_is_the_kernel_it_was():
    """``window=None`` traces the kernel as before this argument existed: the
    same jaxpr as a call that does not pass it."""
    args, _ = _window_case([(40, 1), (0, 0), (300, 1)], bs=16, P=24, KV=2, group=4, D=128,
                    dtype=jnp.bfloat16)
    q = jnp.zeros((3, 2, 4, 128), jnp.bfloat16)
    lengths = jnp.asarray([41, 0, 301], jnp.int32)
    call = functools.partial(pd.paged_decode, scale=0.1, interpret=True)
    a = jax.make_jaxpr(lambda *x: call(*x))(q, args[1], args[2], lengths, args[7])
    b = jax.make_jaxpr(lambda *x: call(*x, window=None))(q, args[1], args[2], lengths, args[7])
    assert str(a) == str(b)
