"""paddle.static.nn tests: static control flow
lowering to lax.cond/lax.while_loop in all three execution worlds, plus the
parameter-creating layer functions and padded-batch sequence ops."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.static.nn as snn


@pytest.fixture()
def static_mode():
    P.enable_static()
    yield
    P.disable_static()


def fresh():
    return P.static.Program()


class TestCondEager:
    def test_basic(self):
        x = P.to_tensor(np.array(3.0, np.float32))
        assert float(snn.cond(P.to_tensor(True), lambda: x + 1, lambda: x - 1).numpy()) == 4.0
        assert float(snn.cond(P.to_tensor(False), lambda: x + 1, lambda: x - 1).numpy()) == 2.0

    def test_tuple_outputs(self):
        x = P.to_tensor(np.ones(3, np.float32))
        a, b = snn.cond(P.to_tensor(True), lambda: (x + 1, x * 2), lambda: (x - 1, x / 2))
        np.testing.assert_allclose(a.numpy(), 2.0)
        np.testing.assert_allclose(b.numpy(), 2.0)


class TestCondStatic:
    def test_cond_in_program(self, static_mode):
        main = fresh()
        with P.static.program_guard(main):
            x = P.static.data("x", [4], "float32")
            flag = P.static.data("flag", [1], "bool")
            out = snn.cond(flag, lambda: x * 2.0, lambda: x + 10.0)
        exe = P.static.Executor()
        xv = np.array([1, 2, 3, 4], np.float32)
        (o1,) = exe.run(main, feed={"x": xv, "flag": np.array([True])}, fetch_list=[out])
        np.testing.assert_allclose(o1, xv * 2)
        (o2,) = exe.run(main, feed={"x": xv, "flag": np.array([False])}, fetch_list=[out])
        np.testing.assert_allclose(o2, xv + 10)

    def test_cond_structure_mismatch_raises(self, static_mode):
        main = fresh()
        with P.static.program_guard(main):
            x = P.static.data("x", [4], "float32")
            flag = P.static.data("flag", [1], "bool")
            with pytest.raises(ValueError):
                snn.cond(flag, lambda: (x, x), lambda: x)

    def test_while_loop_in_program(self, static_mode):
        main = fresh()
        with P.static.program_guard(main):
            x = P.static.data("x", [3], "float32")
            i = P.static.data("i", [1], "int32")
            # run body until i == 4, accumulating x
            iv, acc = snn.while_loop(
                lambda i, acc: i < 4,
                lambda i, acc: (i + 1, acc + x),
                (i, P.zeros([3])),
            )
        exe = P.static.Executor()
        xv = np.array([1.0, 2.0, 3.0], np.float32)
        o_i, o_acc = exe.run(main, feed={"x": xv, "i": np.array([0], np.int32)},
                             fetch_list=[iv, acc])
        assert int(np.reshape(o_i, ())) == 4
        np.testing.assert_allclose(o_acc, xv * 4)

    def test_trains_through_cond_and_while(self, static_mode):
        # done-criterion: train a static model containing a cond AND
        # a while_loop
        main = fresh()
        with P.static.program_guard(main):
            x = P.static.data("x", [8, 4], "float32")
            y = P.static.data("y", [8, 1], "float32")
            flag = P.static.data("flag", [1], "bool")
            lin = P.nn.Linear(4, 1)
            h = lin(x)
            # cond scales the head; while_loop applies 3 refinement steps
            h = snn.cond(flag, lambda: h * 1.0, lambda: h * 0.5)
            # max_iters makes the loop reverse-differentiable (masked scan)
            _, h = snn.while_loop(lambda i, v: i < 3,
                                  lambda i, v: (i + 1, v * 0.9),
                                  (P.zeros([1], dtype="int32"), h), max_iters=4)
            loss = P.mean((h - y) ** 2)
            opt = P.optimizer.SGD(learning_rate=0.1, parameters=[lin.weight, lin.bias])
            opt.minimize(loss)
        exe = P.static.Executor()
        rng = np.random.RandomState(0)
        xv = rng.randn(8, 4).astype(np.float32)
        yv = (xv.sum(1, keepdims=True) * 0.3).astype(np.float32)
        losses = []
        for _ in range(12):
            (lv,) = exe.run(main, feed={"x": xv, "y": yv, "flag": np.array([True])},
                            fetch_list=[loss])
            losses.append(float(np.reshape(lv, ())))
        assert losses[-1] < losses[0] * 0.7

    def test_while_max_iters_dead_branch_gradient_safe(self):
        """ADVICE r4 (double-where): the body also executes on dead
        iterations after the condition goes False; with a domain-constrained
        body (sqrt of a shrinking value) the dead-branch NaN residuals must
        not poison reverse-mode gradients."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.static.nn.control_flow import _lower_while

        def run(x0):
            out = _lower_while(
                lambda c: c[0] < 2,
                lambda c: (c[0] + 1, jnp.sqrt(c[1]) - 0.8),
                (jnp.int32(0), x0), 4)
            return out[1]

        v, g = jax.value_and_grad(run)(jnp.float32(1.0))
        # live iterations: 1 -> sqrt(1)-0.8=0.2 -> sqrt(0.2)-0.8 (negative:
        # a further body application would NaN)
        np.testing.assert_allclose(float(v), np.sqrt(0.2) - 0.8, rtol=1e-5)
        expect_g = 1.0 / (2 * np.sqrt(0.2)) * 0.5
        assert np.isfinite(float(g))
        np.testing.assert_allclose(float(g), expect_g, rtol=1e-4)

    def test_while_max_iters_entry_false_gradient(self):
        """Condition already False at entry: the body need not be total at
        carry0; loop_vars pass through with identity gradient."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.static.nn.control_flow import _lower_while

        def run(x0):
            out = _lower_while(
                lambda c: c[1] > 0,
                lambda c: (c[0] + 1, jnp.sqrt(c[1]) - 1.0),  # NaN at x0<0
                (jnp.int32(0), x0), 3)
            return out[1]

        v, g = jax.value_and_grad(run)(jnp.float32(-2.0))
        np.testing.assert_allclose(float(v), -2.0)
        np.testing.assert_allclose(float(g), 1.0)


class TestCondTraced:
    def test_cond_under_to_static(self):
        @P.jit.to_static
        def f(x, flag):
            return snn.cond(flag, lambda: x * 2.0, lambda: x + 10.0)

        x = P.to_tensor(np.array([1.0, 2.0], np.float32))
        np.testing.assert_allclose(
            f(x, P.to_tensor(np.array([True]))).numpy(), [2.0, 4.0])
        np.testing.assert_allclose(
            f(x, P.to_tensor(np.array([False]))).numpy(), [11.0, 12.0])

    def test_while_under_to_static(self):
        @P.jit.to_static
        def f(x, n):
            _, out = snn.while_loop(lambda i, v: i < n,
                                    lambda i, v: (i + 1, v * 2.0),
                                    (P.zeros([1], dtype="int32"), x))
            return out

        x = P.to_tensor(np.ones(2, np.float32))
        np.testing.assert_allclose(f(x, P.to_tensor(np.array([3], np.int32))).numpy(), 8.0)


class TestCaseSwitch:
    def test_case_eager(self):
        x = P.to_tensor(np.array(1.0, np.float32))
        out = snn.case([(P.to_tensor(False), lambda: x + 1),
                        (P.to_tensor(True), lambda: x + 2)],
                       default=lambda: x + 3)
        assert float(out.numpy()) == 3.0
        # default = last pair when none given
        out = snn.case([(P.to_tensor(False), lambda: x + 1),
                        (P.to_tensor(False), lambda: x + 2)])
        assert float(out.numpy()) == 3.0

    def test_switch_case_eager(self):
        x = P.to_tensor(np.ones(2, np.float32))
        fns = {1: lambda: x * 1, 2: lambda: x * 2, 3: lambda: x * 3}
        out = snn.switch_case(P.to_tensor(np.array(2, np.int64)), fns)
        np.testing.assert_allclose(out.numpy(), 2.0)
        # unmatched index falls through to the highest branch
        out = snn.switch_case(P.to_tensor(np.array(9, np.int64)), fns)
        np.testing.assert_allclose(out.numpy(), 3.0)

    def test_switch_case_static(self, static_mode):
        main = fresh()
        with P.static.program_guard(main):
            x = P.static.data("x", [2], "float32")
            idx = P.static.data("idx", [1], "int64")
            out = snn.switch_case(idx, [(0, lambda: x), (1, lambda: x * 10.0)])
        exe = P.static.Executor()
        xv = np.array([1.0, 2.0], np.float32)
        (o,) = exe.run(main, feed={"x": xv, "idx": np.array([1], np.int64)},
                       fetch_list=[out])
        np.testing.assert_allclose(o, xv * 10)


class TestStaticPyLayerAndPyFunc:
    def test_static_pylayer_custom_backward(self):
        x = P.to_tensor(np.array([2.0, 3.0], np.float32))
        x.stop_gradient = False
        # forward: x**2 ; custom backward: constant 7 per element
        out = snn.static_pylayer(lambda t: t * t, [x],
                                 backward_fn=lambda g: g * 0 + 7.0)
        loss = out.sum()
        loss.backward()
        np.testing.assert_allclose(np.asarray(x.grad.numpy()), [7.0, 7.0])

    def test_py_func_host_roundtrip(self):
        x = P.to_tensor(np.array([1.0, 2.0], np.float32))
        spec = P.zeros([2])
        out = snn.py_func(lambda a: np.asarray(a) * 5.0, x, spec)
        np.testing.assert_allclose(out.numpy(), [5.0, 10.0])


class TestLayerFns:
    def test_fc(self):
        x = P.to_tensor(np.random.randn(4, 6).astype(np.float32))
        out = snn.fc(x, 3)
        assert tuple(out.shape) == (4, 3)
        out = snn.fc(x, 3, activation="relu")
        assert float(np.asarray(out.numpy()).min()) >= 0

    def test_embedding_and_sparse(self):
        ids = P.to_tensor(np.array([[1], [4]], np.int64))
        out = snn.embedding(ids, (10, 8))
        assert tuple(out.shape) == (2, 1, 8)
        from paddle_tpu.distributed import CountFilterEntry

        out = snn.sparse_embedding(ids, (10, 8), entry=CountFilterEntry(2))
        assert tuple(out.shape) == (2, 1, 8)

    def test_conv_family(self):
        x = P.to_tensor(np.random.randn(2, 3, 8, 8).astype(np.float32))
        assert tuple(snn.conv2d(x, 4, 3, padding=1).shape) == (2, 4, 8, 8)
        assert tuple(snn.conv2d_transpose(x, 4, filter_size=2, stride=2).shape) == (2, 4, 16, 16)
        v = P.to_tensor(np.random.randn(1, 2, 4, 4, 4).astype(np.float32))
        assert tuple(snn.conv3d(v, 3, 3, padding=1).shape) == (1, 3, 4, 4, 4)

    def test_norms(self):
        x = P.to_tensor(np.random.randn(2, 4, 5, 5).astype(np.float32))
        assert tuple(snn.batch_norm(x).shape) == (2, 4, 5, 5)
        assert tuple(snn.group_norm(x, 2).shape) == (2, 4, 5, 5)
        assert tuple(snn.instance_norm(x).shape) == (2, 4, 5, 5)
        y = P.to_tensor(np.random.randn(3, 6).astype(np.float32))
        out = snn.layer_norm(y)
        np.testing.assert_allclose(np.asarray(out.numpy()).mean(1), 0, atol=1e-5)
        z = P.to_tensor(np.random.randn(4, 3).astype(np.float32))
        assert tuple(snn.data_norm(z).shape) == (4, 3)

    def test_spectral_norm_scales_to_unit_sigma(self):
        w = P.to_tensor((np.random.randn(6, 4) * 3).astype(np.float32))
        wn = snn.spectral_norm(w, power_iters=20)
        s = np.linalg.svd(np.asarray(wn.numpy()), compute_uv=False)
        assert abs(s[0] - 1.0) < 0.05

    def test_misc_ops(self):
        x = P.to_tensor(np.random.randn(3, 4).astype(np.float32))
        y = P.to_tensor(np.random.randn(3, 5).astype(np.float32))
        assert tuple(snn.bilinear_tensor_product(x, y, 6).shape) == (3, 6)
        assert tuple(snn.prelu(P.to_tensor(np.random.randn(2, 3, 4, 4).astype(np.float32)),
                               mode="channel").shape) == (2, 3, 4, 4)
        seq = P.to_tensor(np.random.randn(2, 5, 3).astype(np.float32))
        assert tuple(snn.row_conv(seq, 2).shape) == (2, 5, 3)
        lbl = P.to_tensor(np.array([[1], [3], [0]], np.int64))
        loss = snn.nce(x, lbl, num_total_classes=10, num_neg_samples=4)
        assert tuple(loss.shape) == (3, 1) and np.all(np.asarray(loss.numpy()) > 0)


class TestSequenceOps:
    def test_pool_family(self):
        x = P.to_tensor(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
        ln = P.to_tensor(np.array([2, 3], np.int64))
        np.testing.assert_allclose(snn.sequence_first_step(x).numpy(), x.numpy()[:, 0])
        np.testing.assert_allclose(snn.sequence_last_step(x, length=ln).numpy()[0],
                                   x.numpy()[0, 1])
        s = snn.sequence_pool(x, "sum", length=ln)
        np.testing.assert_allclose(s.numpy()[0], x.numpy()[0, :2].sum(0))
        m = snn.sequence_pool(x, "max", length=ln)
        np.testing.assert_allclose(m.numpy()[0], x.numpy()[0, :2].max(0))
        a = snn.sequence_pool(x, "average", length=ln)
        np.testing.assert_allclose(a.numpy()[1], x.numpy()[1].mean(0))

    def test_softmax_masked(self):
        x = P.to_tensor(np.zeros((1, 4, 1), np.float32))
        out = snn.sequence_softmax(x, length=P.to_tensor(np.array([2], np.int64)))
        np.testing.assert_allclose(np.asarray(out.numpy())[0, :, 0],
                                   [0.5, 0.5, 0.0, 0.0], atol=1e-6)

    def test_pad_unpad_roundtrip(self):
        x = P.to_tensor(np.ones((2, 3, 2), np.float32))
        ln = P.to_tensor(np.array([1, 3], np.int64))
        padded, lengths = snn.sequence_pad(x, -1.0, maxlen=5, length=ln)
        assert tuple(padded.shape) == (2, 5, 2)
        assert np.asarray(padded.numpy())[0, 1, 0] == -1.0  # beyond row length
        np.testing.assert_allclose(lengths.numpy(), [1, 3])
        unp = snn.sequence_unpad(padded, lengths)
        assert np.asarray(unp.numpy())[0, 1, 0] == 0.0  # masked back out

    def test_conv_slice_misc(self):
        x = P.to_tensor(np.random.randn(2, 6, 3).astype(np.float32))
        assert tuple(snn.sequence_conv(x, 5, filter_size=3).shape) == (2, 6, 5)
        sl = snn.sequence_slice(x, P.to_tensor(np.array([1, 2], np.int64)),
                                P.to_tensor(np.array([2, 2], np.int64)))
        np.testing.assert_allclose(np.asarray(sl.numpy())[0, :2], x.numpy()[0, 1:3])
        r = snn.sequence_reshape(P.to_tensor(np.arange(12, dtype=np.float32).reshape(1, 6, 2)), 4)
        assert tuple(r.shape) == (1, 3, 4)
        e = snn.sequence_enumerate(P.to_tensor(np.array([[1, 2, 3]], np.int64)), 2, pad_value=0)
        np.testing.assert_allclose(e.numpy()[0], [[1, 2], [2, 3], [3, 0]])
        sc = snn.sequence_scatter(P.to_tensor(np.zeros((1, 5), np.float32)),
                                  P.to_tensor(np.array([[1, 3]], np.int64)),
                                  P.to_tensor(np.array([[2.0, 4.0]], np.float32)))
        np.testing.assert_allclose(sc.numpy()[0], [0, 2, 0, 4, 0])
        ex = snn.sequence_expand(P.to_tensor(np.ones((2, 3), np.float32)),
                                 P.to_tensor(np.ones((4, 3), np.float32)))
        assert tuple(ex.shape) == (4, 3)
