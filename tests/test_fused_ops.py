"""The Llama block's elementwise pieces: the rotary embedding as the model
applies it (``apply_rotary_pos_emb``) and the SwiGLU of ``LlamaMLP``, each ONE
op with its own backward; the Pallas rope and SwiGLU kernels (interpret mode)
vs jnp references; and which form a traced training step holds: the kernels on
one chip, the reference forms under a mesh.

Reference analogs: incubate/nn/functional/fused_rotary_position_embedding.py,
swiglu.py (CUDA fused kernels in paddle/phi/kernels/fusion/gpu/).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.models import llama
from paddle_tpu.models.llama import apply_rotary_pos_emb
from paddle_tpu.ops.pallas import fused_ops
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


# --------------------------------------------------- the Pallas rope kernel
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


# ------------------------------ the model's calls, through the kernels (bf16)
def _through_the_kernels(monkeypatch):
    """The model's two ops run their Pallas kernels (interpret mode), as a
    chip runs them."""
    monkeypatch.setattr(llama, "rope_fused", lambda q, k, c, s: rope_fused(q, k, c, s, True))
    monkeypatch.setattr(llama, "swiglu_fused", lambda a, b: swiglu_fused(a, b, True))


def _within_one_bf16_step(got, want, what):
    """Each element within one bf16 rounding step (2**-7 of a value) of the
    written-out float32 value; a value under the tensor's typical size counts
    as of that size (a sum that cancels is no finer than its terms)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    step = 2.0 ** -7 * np.maximum(np.abs(want), np.sqrt(np.mean(want ** 2)))
    worst = float(np.max(np.abs(got - want) / step))
    assert worst <= 1.0, f"{what}: {worst:.2f} bf16 steps off"


def test_rope_through_the_kernel_is_the_written_out_rotation_in_bf16(monkeypatch):
    _through_the_kernels(monkeypatch)
    q, k, cos, sin = _rope_inputs(s=16, table=64)
    q, k = (np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32) for x in (q, k))
    off = 9
    rng = np.random.RandomState(6)
    wq, wk = rng.randn(*q.shape).astype(np.float32), rng.randn(*k.shape).astype(np.float32)
    wq, wk = (np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32) for x in (wq, wk))
    tq, tk = P.to_tensor(q).astype("bfloat16"), P.to_tensor(k).astype("bfloat16")
    tq.stop_gradient = tk.stop_gradient = False
    oq, ok = apply_rotary_pos_emb(tq, tk, P.to_tensor(cos), P.to_tensor(sin),
                                  position_offset=off)
    assert oq._value.dtype == jnp.bfloat16 and ok._value.dtype == jnp.bfloat16
    ((oq * P.to_tensor(wq).astype("bfloat16")).sum()
     + (ok * P.to_tensor(wk).astype("bfloat16")).sum()).backward()
    c, s = cos[off:off + 16], sin[off:off + 16]
    _within_one_bf16_step(oq._value, _rotated(q, c, s), "q")
    _within_one_bf16_step(ok._value, _rotated(k, c, s), "k")
    _within_one_bf16_step(tq.grad._value, _rotated(wq, c, -s), "dq")
    _within_one_bf16_step(tk.grad._value, _rotated(wk, c, -s), "dk")


def test_mlp_through_the_kernels_is_the_written_out_swiglu_in_bf16(monkeypatch):
    """``LlamaMLP.forward`` at a step of 16 tokens: its output and the
    gradients of its input and three matrices against ``down(silu(gate x) *
    up x)`` written out over the same bf16 products, the activation in
    float32 and rounded once."""
    _through_the_kernels(monkeypatch)
    P.seed(3)
    mlp = llama.LlamaMLP(llama.llama_tiny())
    mlp.bfloat16()
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 16, 128), jnp.bfloat16)
    w = jnp.asarray(rng.randn(2, 16, 128), jnp.bfloat16)
    tx = P.to_tensor(x)
    tx.stop_gradient = False
    out = mlp(tx)
    assert out._value.dtype == jnp.bfloat16
    (out * P.to_tensor(w)).sum().backward()
    mats = [mlp.gate_proj.weight, mlp.up_proj.weight, mlp.down_proj.weight]

    def written_out(xv, wg, wu, wd):
        g, u = (xv @ wg).astype(jnp.float32), (xv @ wu).astype(jnp.float32)
        return (g * jax.nn.sigmoid(g) * u).astype(xv.dtype) @ wd

    vals = [m._value for m in mats]
    want = written_out(x, *vals)
    grads = jax.grad(lambda *a: jnp.sum((written_out(*a) * w).astype(jnp.float32)),
                     argnums=(0, 1, 2, 3))(x, *vals)
    _within_one_bf16_step(out._value, want, "out")
    for name, got, ref in zip(("dx", "dgate", "dup", "ddown"),
                              [tx.grad] + [m.grad for m in mats], grads):
        _within_one_bf16_step(got._value, ref, name)


def test_a_one_token_step_keeps_its_joined_gate_up_product():
    """Generation's shape, not training's: one token a row multiplies gate|up
    as one streamed matrix; a longer step makes two products and joins
    nothing."""
    P.seed(3)
    mlp = llama.LlamaMLP(llama.llama_tiny())

    def traced(tokens):
        return str(jax.make_jaxpr(lambda v: mlp(P.to_tensor(v))._value)(
            P.randn([2, tokens, 128])._value))

    one, four = traced(1), traced(4)
    assert "concatenate" in one and one.count("dot_general") == 2
    assert "concatenate" not in four and four.count("dot_general") == 3


# ------------------------------- which form a traced training step holds
_KERNELS = ("fused_rope", "swiglu_fwd", "swiglu_bwd")


def _traced_step_text(mesh):
    """The jaxpr (nothing compiled) of ``TrainStep``'s whole step, loss,
    gradient and AdamW, over a two-layer bf16 ``llama_tiny`` with
    ``recompute``; ``mesh``: under an active dp 2 x mp 2 fleet mesh."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.jit.api import flatten_tensors
    from paddle_tpu.models import LlamaForCausalLM, LlamaPretrainingCriterion

    set_hybrid_communicate_group(None)
    try:
        if mesh:
            strategy = dist.fleet.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                                       "sharding_degree": 1, "sep_degree": 1}
            dist.fleet.init(is_collective=True, strategy=strategy)
        P.seed(0)
        model = LlamaForCausalLM(llama.llama_tiny(dtype="bfloat16", recompute=True))
        model.bfloat16()
        if mesh:
            model = dist.fleet.distributed_model(model)
        crit = LlamaPretrainingCriterion()
        opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                                multi_precision=True)
        step = P.jit.TrainStep(model, lambda m, ids: crit(m(ids), ids), opt)
        tensors, spec = flatten_tensors((P.to_tensor(np.zeros((2, 32), np.int32)),))
        step._build(spec)
        accs, masters = step._get_opt_state()
        return str(jax.make_jaxpr(step._step_raw)(
            [p._value for p in step._params], accs, masters,
            [b._value for b in step._buffers], step._scaler_state(), jax.random.PRNGKey(0),
            tuple(t._value for t in tensors), jnp.float32(1e-3)))
    finally:
        set_hybrid_communicate_group(None)


@pytest.mark.parametrize("mesh", [False, True], ids=["one-chip", "dp2-mp2"])
def test_the_traced_training_step_holds_the_kernels_on_one_chip_and_none_under_a_mesh(
        mesh, monkeypatch):
    """With the platform answering yes (steered as ``tests/aot/described_device.
    on_the_chip`` steers it; no option), a layer under ``recompute`` rotates q
    and k in its forward, again in its recomputation and once more, by
    ``-sin``, in its backward (6 ``fused_rope`` calls), and runs ``swiglu_fwd``
    twice and ``swiglu_bwd`` once: 12, 4 and 2 in two layers, what a traced
    ``jit_step`` of mistral7b.train.pretrain-2k shows on the chip. Under a
    mesh ``fused_ops._in_kernel`` takes the reference forms, which GSPMD
    partitions: no Pallas call of these names is in the program."""
    monkeypatch.setattr(fused_ops, "on_tpu", lambda: True)
    text = _traced_step_text(mesh)
    calls = {name: len(re.findall(rf"\bname={name}\b", text)) for name in _KERNELS}
    assert calls == (dict.fromkeys(_KERNELS, 0) if mesh else
                     {"fused_rope": 12, "swiglu_fwd": 4, "swiglu_bwd": 2})


@pytest.mark.parametrize("rows,kernel", [(2048, True), (64, True), (8, True), (1, True),
                                         (7, False), (100, False)])
def test_a_length_mosaic_would_refuse_takes_the_reference_form(rows, kernel, monkeypatch):
    """A block's rows are whole sublane tiles of 8 or all the rows there are:
    a prompt of 7 or 100 tokens (the growing cache's prefill) compiles on the
    chip because ``_in_kernel`` sends it to the reference form."""
    monkeypatch.setattr(fused_ops, "on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((1, rows, 4, 32), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, rows, 2, 32), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((rows, 16), jnp.float32)
    act = jax.ShapeDtypeStruct((1, rows, 352), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, c, s, a, b: (rope_fused(q, k, c, s),
                                                       swiglu_fused(a, b)))(
        q, k, table, table, act, act))
    assert ("name=fused_rope" in text) == kernel and ("name=swiglu_fwd" in text) == kernel
