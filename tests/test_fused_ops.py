"""The Llama block's elementwise pieces: the rotary embedding as the model
applies it by default (``apply_rotary_pos_emb``, jnp), and the Pallas rope and
SwiGLU kernels (interpret mode) vs jnp references.

Reference analogs: incubate/nn/functional/fused_rotary_position_embedding.py,
swiglu.py (CUDA fused kernels in paddle/phi/kernels/fusion/gpu/).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.models.llama import apply_rotary_pos_emb
from paddle_tpu.ops.pallas.fused_ops import _rope_ref, rope_fused, swiglu_fused


def _rope_inputs(b=2, s=64, h=4, hk=2, d=32, table=None):
    """q, k and cos/sin tables of ``table`` rows (the sequence's own length
    unless given)."""
    rng = np.random.RandomState(0)
    q = rng.randn(b, s, h, d).astype(np.float32)
    k = rng.randn(b, s, hk, d).astype(np.float32)
    inv = 1.0 / (10000.0 ** (np.arange(0, d, 2) / d))
    fr = np.outer(np.arange(table or s), inv)
    return q, k, np.cos(fr).astype(np.float32), np.sin(fr).astype(np.float32)


def _rotated(x, cos, sin):
    """The rotation written out: pair (i, i + D/2) of position t turns by the
    angle whose cosine and sine are row t of the tables."""
    half = x.shape[-1] // 2
    out = np.empty_like(x)
    for t in range(x.shape[1]):
        for i in range(half):
            lo, hi = x[:, t, :, i], x[:, t, :, i + half]
            out[:, t, :, i] = lo * cos[t, i] - hi * sin[t, i]
            out[:, t, :, i + half] = hi * cos[t, i] + lo * sin[t, i]
    return out


def _apply(q, k, cos, sin, **kw):
    return apply_rotary_pos_emb(P.to_tensor(q), P.to_tensor(k), P.to_tensor(cos),
                                P.to_tensor(sin), **kw)


def test_rope_matches_the_written_out_rotation():
    q, k, cos, sin = _rope_inputs()
    oq, ok = _apply(q, k, cos, sin)
    np.testing.assert_allclose(oq.numpy(), _rotated(q, cos, sin), atol=1e-5)
    np.testing.assert_allclose(ok.numpy(), _rotated(k, cos, sin), atol=1e-5)


def test_rope_grad_is_the_rotation_back():
    # d/dx of a rotation by theta is a rotation of the cotangent by -theta
    q, k, cos, sin = _rope_inputs(s=32)
    rng = np.random.RandomState(5)
    wq, wk = rng.randn(*q.shape).astype(np.float32), rng.randn(*k.shape).astype(np.float32)
    tq, tk = P.to_tensor(q), P.to_tensor(k)
    tq.stop_gradient = tk.stop_gradient = False
    oq, ok = apply_rotary_pos_emb(tq, tk, P.to_tensor(cos), P.to_tensor(sin))
    ((oq * P.to_tensor(wq)).sum() + (ok * P.to_tensor(wk)).sum()).backward()
    np.testing.assert_allclose(tq.grad.numpy(), _rotated(wq, cos, -sin), atol=1e-4)
    np.testing.assert_allclose(tk.grad.numpy(), _rotated(wk, cos, -sin), atol=1e-4)


def test_rope_rotation_invariant():
    # a rotation preserves per-pair norms
    q, k, cos, sin = _rope_inputs()
    oq = _apply(q, k, cos, sin)[0].numpy()
    d = q.shape[-1] // 2
    n_in = q[..., :d] ** 2 + q[..., d:] ** 2
    n_out = oq[..., :d] ** 2 + oq[..., d:] ** 2
    np.testing.assert_allclose(n_in, n_out, atol=1e-4)


@pytest.mark.parametrize("traced", [False, True], ids=["int", "tensor"])
def test_rope_position_offset_reads_the_tables_rows_from_there(traced):
    """What the growing cache asks (an int: the cached length) and the static
    ring (a scalar Tensor): S new tokens at offset p turn by rows p..p+S-1."""
    q, k, cos, sin = _rope_inputs(s=3, table=64)
    off = 41
    oq, ok = _apply(q, k, cos, sin,
                    position_offset=P.to_tensor(np.int32(off)) if traced else off)
    np.testing.assert_allclose(oq.numpy(), _rotated(q, cos[off:off + 3], sin[off:off + 3]),
                               atol=1e-5)
    np.testing.assert_allclose(ok.numpy(), _rotated(k, cos[off:off + 3], sin[off:off + 3]),
                               atol=1e-5)
    assert np.abs(oq.numpy() - _rotated(q, cos, sin)).max() > 0.1


# ------------------------------- the Pallas rope kernel (PADDLE_TPU_FUSED_LLAMA)
def _kernel_rope_inputs(**kw):
    return map(jnp.asarray, _rope_inputs(**kw))


def test_rope_kernel_matches_ref():
    q, k, cos, sin = _kernel_rope_inputs()
    oq, ok = rope_fused(q, k, cos, sin, True)
    rq, rk = _rope_ref(q, k, cos, sin)
    np.testing.assert_allclose(np.asarray(oq), np.asarray(rq), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(rk), atol=1e-5)


def test_rope_kernel_grad_matches_ref():
    q, k, cos, sin = _kernel_rope_inputs(s=32)

    def loss_kernel(q, k):
        oq, ok = rope_fused(q, k, cos, sin, True)
        return jnp.sum(oq * oq) + jnp.sum(ok * jnp.cos(ok))

    def loss_ref(q, k):
        oq, ok = _rope_ref(q, k, cos, sin)
        return jnp.sum(oq * oq) + jnp.sum(ok * jnp.cos(ok))

    gk = jax.grad(loss_kernel, argnums=(0, 1))(q, k)
    gr = jax.grad(loss_ref, argnums=(0, 1))(q, k)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_rope_kernel_rotation_invariant():
    # a rotation preserves per-pair norms
    q, k, cos, sin = _kernel_rope_inputs()
    oq, _ = rope_fused(q, k, cos, sin, True)
    d = q.shape[-1] // 2
    n_in = np.asarray(q[..., :d] ** 2 + q[..., d:] ** 2)
    n_out = np.asarray(oq[..., :d] ** 2 + oq[..., d:] ** 2)
    np.testing.assert_allclose(n_in, n_out, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_kernel_matches_ref(dtype):
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(8, 96), dtype)
    b = jnp.asarray(rng.randn(8, 96), dtype)
    out = swiglu_fused(a, b, True)
    ref = (jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)).astype(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_swiglu_kernel_grads():
    rng = np.random.RandomState(2)
    a = jnp.asarray(rng.randn(4, 64), jnp.float32)
    b = jnp.asarray(rng.randn(4, 64), jnp.float32)

    gk = jax.grad(lambda a, b: jnp.sum(jnp.tanh(swiglu_fused(a, b, True))), argnums=(0, 1))(a, b)
    gr = jax.grad(lambda a, b: jnp.sum(jnp.tanh(jax.nn.silu(a) * b)), argnums=(0, 1))(a, b)
    for x, y in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-4)


def test_incubate_swiglu_entry():
    import paddle_tpu as P
    from paddle_tpu.incubate.nn import functional as IF

    x = P.randn([4, 32])
    y = P.randn([4, 32])
    out = IF.swiglu(x, y)
    ref = jax.nn.silu(x._value) * y._value
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref), atol=1e-5)
    # single-arg split form
    out2 = IF.swiglu(P.concat([x, y], axis=-1))
    np.testing.assert_allclose(np.asarray(out2._value), np.asarray(ref), atol=1e-5)


def test_llama_model_with_fused_ops_trains():
    import paddle_tpu as P
    from paddle_tpu.models import (
        LlamaForCausalLM,
        LlamaPretrainingCriterion,
        llama_tiny,
    )

    P.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    crit = LlamaPretrainingCriterion()
    opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = P.jit.TrainStep(model, lambda m, ids: crit(m(ids), ids), opt)
    ids = P.to_tensor(np.random.RandomState(0).randint(0, 512, (2, 32)).astype(np.int32))
    l0 = float(step(ids).numpy())
    for _ in range(3):
        l1 = float(step(ids).numpy())
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0  # learning


def test_fused_lm_loss_matches_criterion():
    import paddle_tpu as P
    from paddle_tpu.models import (
        LlamaForCausalLM,
        LlamaPretrainingCriterion,
        llama_tiny,
    )

    P.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    ids = P.to_tensor(np.random.RandomState(3).randint(0, 512, (2, 33)).astype(np.int32))
    crit = LlamaPretrainingCriterion()
    ref = float(crit(model(ids), ids).numpy())
    fused = float(model.pretraining_loss(ids, n_chunks=4).numpy())
    np.testing.assert_allclose(fused, ref, rtol=2e-3)
    # and it trains
    opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = P.jit.TrainStep(model, lambda m, i: m.pretraining_loss(i, n_chunks=4), opt)
    l0 = float(step(ids).numpy())
    for _ in range(3):
        l1 = float(step(ids).numpy())
    assert l1 < l0
