"""Autograd tests: analytic grads vs finite differences — the reference's
check_grad discipline (/root/reference/test/legacy_test/op_test.py:148
get_numeric_gradient)."""
import numpy as np
import pytest

import paddle_tpu as P


def numeric_grad(fn, x, eps=1e-3):
    """Central finite differences of scalar fn at x (numpy array)."""
    g = np.zeros_like(x, dtype=np.float64)
    flat = x.reshape(-1)
    gf = g.reshape(-1)
    for i in range(flat.size):
        orig = flat[i]
        flat[i] = orig + eps
        f1 = fn(x.copy().reshape(x.shape))
        flat[i] = orig - eps
        f2 = fn(x.copy().reshape(x.shape))
        flat[i] = orig
        gf[i] = (f1 - f2) / (2 * eps)
    return g


def check_grad(op, x_np, rtol=1e-2, atol=1e-3):
    x = P.to_tensor(x_np.astype(np.float32), stop_gradient=False)
    out = op(x)
    loss = P.sum(out)
    loss.backward()
    analytic = x.grad.numpy().astype(np.float64)

    def f(a):
        return float(P.sum(op(P.to_tensor(a.astype(np.float32)))).numpy())

    numeric = numeric_grad(f, x_np.astype(np.float64))
    np.testing.assert_allclose(analytic, numeric, rtol=rtol, atol=atol)


class TestNumericGradients:
    def test_unary_ops(self):
        x = np.random.rand(3, 4) + 0.5
        check_grad(lambda t: P.exp(t), x)
        check_grad(lambda t: P.log(t), x)
        check_grad(lambda t: P.sqrt(t), x)
        check_grad(lambda t: P.tanh(t), x)
        check_grad(lambda t: P.sigmoid(t) if hasattr(P, "sigmoid") else P.tanh(t), x)
        check_grad(lambda t: t * t * t, x)

    @pytest.mark.quick
    def test_matmul_grad(self):
        w = np.random.randn(4, 5)
        check_grad(lambda t: P.matmul(t, P.to_tensor(w.astype(np.float32))), np.random.randn(3, 4))

    def test_reduction_grads(self):
        x = np.random.randn(3, 4)
        check_grad(lambda t: P.mean(t, axis=1), x)
        check_grad(lambda t: P.max(t, axis=0), x)
        check_grad(lambda t: P.logsumexp(t), x)

    def test_composite(self):
        x = np.random.rand(4, 4) + 0.1
        check_grad(lambda t: P.sum(P.exp(t) / (1.0 + P.exp(t)), axis=1), x)


class TestBackwardSemantics:
    def test_accumulation(self):
        x = P.to_tensor([2.0], stop_gradient=False)
        y = x * 3
        z = x * 4
        (y + z).backward()
        assert x.grad.item() == 7.0

    def test_grad_accumulates_across_backwards(self):
        x = P.to_tensor([1.0], stop_gradient=False)
        (x * 2).backward()
        (x * 3).backward()
        assert x.grad.item() == 5.0

    def test_clear_grad(self):
        x = P.to_tensor([1.0], stop_gradient=False)
        (x * 2).backward()
        x.clear_grad()
        assert x.grad is None

    def test_stop_gradient_blocks(self):
        x = P.to_tensor([1.0], stop_gradient=False)
        y = (x * 2).detach()
        z = y * 3
        z.backward()
        assert x.grad is None

    def test_no_grad_context(self):
        x = P.to_tensor([1.0], stop_gradient=False)
        with P.no_grad():
            y = x * 2
        assert y.stop_gradient
        assert y._grad_node is None

    def test_retain_graph(self):
        x = P.to_tensor([2.0], stop_gradient=False)
        y = x * x
        y.backward(retain_graph=True)
        y.backward()
        assert x.grad.item() == 8.0

    def test_double_backward_without_retain_raises(self):
        x = P.to_tensor([2.0], stop_gradient=False)
        y = x * x
        y.backward()
        with pytest.raises(RuntimeError):
            y.backward()

    def test_multi_output_op(self):
        x = P.to_tensor(np.arange(6, dtype=np.float32), stop_gradient=False)
        a, b = P.split(x, 2)
        (a.sum() * 2 + b.sum() * 3).backward()
        np.testing.assert_allclose(x.grad.numpy(), [2, 2, 2, 3, 3, 3])

    def test_backward_with_grad_tensor(self):
        x = P.to_tensor([1.0, 2.0], stop_gradient=False)
        y = x * 2
        y.backward(P.to_tensor([1.0, 10.0]))
        np.testing.assert_allclose(x.grad.numpy(), [2.0, 20.0])

    def test_hook(self):
        x = P.to_tensor([1.0], stop_gradient=False)
        seen = []

        def hook(g):
            seen.append(g.numpy().copy())
            return g * 10

        x.register_hook(hook)
        (x * 2).backward()
        assert seen and seen[0][0] == 2.0
        assert x.grad.item() == 20.0

    def test_retain_grads_interior(self):
        x = P.to_tensor([1.0], stop_gradient=False)
        y = x * 2
        y.retain_grads()
        (y * 3).backward()
        assert y.grad.item() == 3.0
        assert x.grad.item() == 6.0


class TestGradAPI:
    def test_paddle_grad(self):
        x = P.to_tensor([3.0], stop_gradient=False)
        y = x * x
        (gx,) = P.grad(y, x)
        assert gx.item() == 6.0
        assert x.grad is None  # paddle.grad does not write .grad

    def test_grad_unused(self):
        x = P.to_tensor([1.0], stop_gradient=False)
        z = P.to_tensor([1.0], stop_gradient=False)
        y = x * 2
        with pytest.raises(RuntimeError):
            P.grad(y, [z])
        y2 = x * 3
        gs = P.grad(y2, [z], allow_unused=True)
        assert gs[0] is None

    def test_grad_multiple_inputs(self):
        x = P.to_tensor([2.0], stop_gradient=False)
        y = P.to_tensor([3.0], stop_gradient=False)
        z = x * y + x
        gx, gy = P.grad(z, [x, y])
        assert gx.item() == 4.0 and gy.item() == 2.0


class TestPyLayer:
    def test_custom_tanh(self):
        class CusTanh(P.autograd.PyLayer):
            @staticmethod
            def forward(ctx, x):
                y = P.tanh(x)
                ctx.save_for_backward(y)
                return y

            @staticmethod
            def backward(ctx, dy):
                (y,) = ctx.saved_tensor()
                return dy * (1 - y * y)

        x = P.to_tensor([0.5], stop_gradient=False)
        out = CusTanh.apply(x)
        out.backward()
        expected = 1 - np.tanh(0.5) ** 2
        np.testing.assert_allclose(x.grad.numpy(), [expected], rtol=1e-5)

    def test_multi_input_pylayer(self):
        class Mul(P.autograd.PyLayer):
            @staticmethod
            def forward(ctx, a, b):
                ctx.save_for_backward(a, b)
                return a * b

            @staticmethod
            def backward(ctx, dy):
                a, b = ctx.saved_tensor()
                return dy * b, dy * a

        a = P.to_tensor([2.0], stop_gradient=False)
        b = P.to_tensor([5.0], stop_gradient=False)
        Mul.apply(a, b).backward()
        assert a.grad.item() == 5.0 and b.grad.item() == 2.0


class TestDoubleGrad:
    """create_graph=True: vjp-of-vjp through the tape."""

    def test_second_derivative_scalar(self):
        x = P.to_tensor(np.float32(2.0))
        x.stop_gradient = False
        y = x * x * x
        (g,) = P.grad(y, x, create_graph=True)
        np.testing.assert_allclose(float(np.asarray(g._value)), 12.0, rtol=1e-5)
        (g2,) = P.grad(g, x)
        np.testing.assert_allclose(float(np.asarray(g2._value)), 12.0, rtol=1e-5)

    def test_grad_penalty(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        w = P.to_tensor(rng.randn(4, 4).astype(np.float32))
        w.stop_gradient = False
        x = P.to_tensor(rng.randn(2, 4).astype(np.float32))
        x.stop_gradient = False
        loss = P.mean(P.matmul(x, w) ** 2)
        (gx,) = P.grad(loss, x, create_graph=True)
        P.sum(gx * gx).backward()
        assert w.grad is not None

        def ref_fn(wv, xv):
            gxv = jax.grad(lambda x_: jnp.mean((x_ @ wv) ** 2))(xv)
            return jnp.sum(gxv * gxv)

        ref = jax.grad(ref_fn)(w._value, x._value)
        np.testing.assert_allclose(np.asarray(w.grad._value), np.asarray(ref), rtol=1e-4)

    def test_third_order(self):
        x = P.to_tensor(np.float32(1.5))
        x.stop_gradient = False
        y = x ** 4
        (g1,) = P.grad(y, x, create_graph=True)
        (g2,) = P.grad(g1, x, create_graph=True)
        (g3,) = P.grad(g2, x)
        np.testing.assert_allclose(float(np.asarray(g3._value)), 24 * 1.5, rtol=1e-5)

    def test_backward_create_graph_accumulates(self):
        x = P.to_tensor(np.float32(3.0))
        x.stop_gradient = False
        (x ** 3).backward(create_graph=True)
        g = x.grad  # 27, tape-connected
        (g * 2.0).backward()  # adds d(2*3x^2)/dx = 12x = 36
        np.testing.assert_allclose(float(np.asarray(x.grad._value)), 63.0, rtol=1e-5)
