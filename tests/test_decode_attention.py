"""Pallas decode-attention kernel (ops/pallas/decode_attention.py): interpret-
mode parity vs the jnp reference, ring-write aliasing semantics, GQA
indexing. (On the real chip the EINSUM decode path is the default — measured
faster than this kernel on v5e at r4 — but the kernel must stay
numerically correct.)"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.decode_attention import (
    decode_attention,
    kv_ring_write,
    ref_decode_attention,
)

RNG = np.random.RandomState(0)


class TestDecodeKernelInterpret:
    @pytest.mark.parametrize("pos", [0, 5, 130, 255])
    def test_matches_reference(self, pos):
        B, H, KVH, D, L = 2, 4, 4, 128, 256
        q = jnp.asarray(RNG.randn(B, 1, H, D), jnp.float32)
        kb = jnp.asarray(RNG.randn(B, L, KVH, D), jnp.float32)
        vb = jnp.asarray(RNG.randn(B, L, KVH, D), jnp.float32)
        out = decode_attention(q, kb, vb, jnp.int32(pos), interpret=True)
        ref = ref_decode_attention(q, kb, vb, jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_gqa_grouped_heads(self):
        B, H, KVH, D, L = 2, 4, 2, 128, 256
        q = jnp.asarray(RNG.randn(B, 1, H, D), jnp.float32)
        kb = jnp.asarray(RNG.randn(B, L, KVH, D), jnp.float32)
        vb = jnp.asarray(RNG.randn(B, L, KVH, D), jnp.float32)
        out = decode_attention(q, kb, vb, jnp.int32(100), interpret=True)
        ref = ref_decode_attention(q, kb, vb, jnp.int32(100))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_ring_write(self):
        B, KVH, D, L = 2, 4, 128, 64
        buf = jnp.asarray(RNG.randn(B, L, KVH, D), jnp.float32)
        new = jnp.asarray(RNG.randn(B, 1, KVH, D), jnp.float32)
        out = kv_ring_write(buf, new, jnp.int32(7), interpret=True)
        ref = buf.at[:, 7].set(new[:, 0])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    def test_under_jit(self):
        B, H, D, L = 2, 4, 128, 256
        q = jnp.asarray(RNG.randn(B, 1, H, D), jnp.float32)
        kb = jnp.asarray(RNG.randn(B, L, H, D), jnp.float32)
        vb = jnp.asarray(RNG.randn(B, L, H, D), jnp.float32)

        @jax.jit
        def f(q, pos):
            return decode_attention(q, kb, vb, pos, interpret=True)

        out = f(q, jnp.int32(50))
        ref = ref_decode_attention(q, kb, vb, jnp.int32(50))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
