"""paddle.sparse parity tests: COO/CSR round-trips,
value ops, spmm/sddmm vs dense reference, gradient flow to values."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.sparse as S


def _v(t):
    return np.asarray(t._value)


RNG = np.random.RandomState(11)


def rand_coo(shape=(4, 5), nnz=6, seed=0):
    rs = np.random.RandomState(seed)
    flat = rs.choice(shape[0] * shape[1], nnz, replace=False)
    idx = np.stack(np.unravel_index(flat, shape))
    vals = rs.randn(nnz).astype(np.float32)
    return S.sparse_coo_tensor(idx, vals, shape), idx, vals


class TestCreationAndConvert:
    def test_coo_to_dense(self):
        sp, idx, vals = rand_coo()
        dense = np.zeros((4, 5), np.float32)
        dense[idx[0], idx[1]] = vals
        np.testing.assert_allclose(_v(sp.to_dense()), dense)

    @pytest.mark.quick
    def test_coo_csr_roundtrip(self):
        sp, idx, vals = rand_coo()
        csr = sp.to_sparse_csr()
        assert csr.nnz == sp.nnz
        np.testing.assert_allclose(_v(csr.to_dense()), _v(sp.to_dense()))
        back = csr.to_sparse_coo()
        np.testing.assert_allclose(_v(back.to_dense()), _v(sp.to_dense()))

    def test_csr_tensor_direct(self):
        crows = [0, 2, 3, 3]
        cols = [0, 2, 1]
        vals = [1.0, 2.0, 3.0]
        csr = S.sparse_csr_tensor(crows, cols, vals, [3, 3])
        expect = np.array([[1, 0, 2], [0, 3, 0], [0, 0, 0]], np.float32)
        np.testing.assert_allclose(_v(csr.to_dense()), expect)

    def test_coalesce_merges_duplicates(self):
        idx = np.array([[0, 0, 1], [1, 1, 2]])
        sp = S.sparse_coo_tensor(idx, [1.0, 2.0, 3.0], [2, 3])
        co = sp.coalesce()
        assert co.nnz == 2
        expect = np.zeros((2, 3), np.float32)
        expect[0, 1] = 3.0
        expect[1, 2] = 3.0
        np.testing.assert_allclose(_v(co.to_dense()), expect)

    def test_infer_shape(self):
        sp = S.sparse_coo_tensor(np.array([[0, 2], [1, 3]]), [1.0, 2.0])
        assert sp.shape == [3, 4]


class TestValueOps:
    @pytest.mark.parametrize("op,ref", [
        (S.sin, np.sin), (S.tanh, np.tanh), (S.square, np.square),
        (S.abs, np.abs), (S.neg, np.negative), (S.expm1, np.expm1),
    ])
    def test_unary(self, op, ref):
        sp, idx, vals = rand_coo()
        out = op(sp)
        np.testing.assert_allclose(_v(out.values()), ref(vals), rtol=1e-5)

    def test_unary_on_csr(self):
        sp, _, vals = rand_coo()
        out = S.tanh(sp.to_sparse_csr())
        assert out.is_sparse_csr
        np.testing.assert_allclose(np.sort(_v(out.values())), np.sort(np.tanh(vals)), rtol=1e-5)

    def test_add_same_pattern(self):
        sp, idx, vals = rand_coo(seed=1)
        sp2 = S.sparse_coo_tensor(idx, vals * 2, [4, 5])
        out = S.add(sp, sp2)
        np.testing.assert_allclose(_v(out.to_dense()), _v(sp.to_dense()) * 3, rtol=1e-5)

    def test_add_pattern_union(self):
        a, _, _ = rand_coo(seed=2)
        b, _, _ = rand_coo(seed=3)
        out = S.add(a, b)
        np.testing.assert_allclose(_v(out.to_dense()), _v(a.to_dense()) + _v(b.to_dense()),
                                   rtol=1e-5)

    def test_multiply_divide(self):
        sp, idx, vals = rand_coo(seed=4)
        sp2 = S.sparse_coo_tensor(idx, np.abs(vals) + 1.0, [4, 5])
        np.testing.assert_allclose(_v(S.multiply(sp, sp2).values()), vals * (np.abs(vals) + 1),
                                   rtol=1e-5)
        np.testing.assert_allclose(_v(S.divide(sp, sp2).values()), vals / (np.abs(vals) + 1),
                                   rtol=1e-5)

    def test_pow_cast_isnan(self):
        sp, _, vals = rand_coo(seed=5)
        np.testing.assert_allclose(_v(S.pow(S.abs(sp), 2.0).values()), np.abs(vals) ** 2, rtol=1e-5)
        assert not _v(S.isnan(sp).values()).any()
        c = S.cast(sp, value_dtype="float16")
        assert "float16" in str(c.dtype)


class TestMatmulTier:
    def test_spmm_vs_dense(self):
        sp, _, _ = rand_coo((4, 5), seed=6)
        d = RNG.randn(5, 3).astype(np.float32)
        out = S.matmul(sp, P.to_tensor(d))
        np.testing.assert_allclose(_v(out), _v(sp.to_dense()) @ d, rtol=1e-4, atol=1e-5)

    def test_csr_spmm(self):
        sp, _, _ = rand_coo((4, 5), seed=7)
        d = RNG.randn(5, 3).astype(np.float32)
        out = S.matmul(sp.to_sparse_csr(), P.to_tensor(d))
        np.testing.assert_allclose(_v(out), _v(sp.to_dense()) @ d, rtol=1e-4, atol=1e-5)

    def test_mv(self):
        sp, _, _ = rand_coo((4, 5), seed=8)
        v = RNG.randn(5).astype(np.float32)
        np.testing.assert_allclose(_v(S.mv(sp, P.to_tensor(v))), _v(sp.to_dense()) @ v,
                                   rtol=1e-4, atol=1e-5)

    def test_sddmm(self):
        mask, idx, _ = rand_coo((4, 5), seed=9)
        a = RNG.randn(4, 6).astype(np.float32)
        b = RNG.randn(6, 5).astype(np.float32)
        out = S.masked_matmul(P.to_tensor(a), P.to_tensor(b), mask)
        full = a @ b
        np.testing.assert_allclose(_v(out.values()), full[idx[0], idx[1]], rtol=1e-4, atol=1e-5)

    def test_addmm(self):
        sp, _, _ = rand_coo((4, 5), seed=10)
        y = RNG.randn(5, 3).astype(np.float32)
        inp = RNG.randn(4, 3).astype(np.float32)
        out = S.addmm(P.to_tensor(inp), sp, P.to_tensor(y), beta=0.5, alpha=2.0)
        np.testing.assert_allclose(_v(out), 0.5 * inp + 2.0 * (_v(sp.to_dense()) @ y),
                                   rtol=1e-4, atol=1e-5)

    def test_spmm_gradient_to_values(self):
        sp, idx, vals = rand_coo((4, 5), seed=12)
        sp.stop_gradient = False
        d = P.to_tensor(RNG.randn(5, 3).astype(np.float32))
        out = S.matmul(sp, d)
        P.sum(out).backward()
        g = sp.values().grad
        assert g is not None
        # d(sum(A@D))/dA_ij = sum_k D_jk
        expect = _v(d).sum(1)[idx[1]]
        np.testing.assert_allclose(_v(g), expect, rtol=1e-4, atol=1e-5)


class TestStructureOps:
    def test_transpose(self):
        sp, _, _ = rand_coo((4, 5), seed=13)
        out = S.transpose(sp, [1, 0])
        np.testing.assert_allclose(_v(out.to_dense()), _v(sp.to_dense()).T)

    def test_sum_axis(self):
        sp, _, _ = rand_coo((4, 5), seed=14)
        out = S.sum(sp, axis=0)
        np.testing.assert_allclose(_v(out.to_dense()), _v(sp.to_dense()).sum(0), rtol=1e-5)
        total = S.sum(sp)
        np.testing.assert_allclose(float(_v(total)), _v(sp.to_dense()).sum(), rtol=1e-5)

    def test_reshape(self):
        sp, _, _ = rand_coo((4, 5), seed=15)
        out = S.reshape(sp, [2, 10])
        np.testing.assert_allclose(_v(out.to_dense()), _v(sp.to_dense()).reshape(2, 10))

    def test_slice(self):
        sp, _, _ = rand_coo((4, 5), seed=16)
        out = S.slice(sp, [0, 1], [1, 1], [3, 4])
        np.testing.assert_allclose(_v(out.to_dense()), _v(sp.to_dense())[1:3, 1:4])

    def test_mask_as(self):
        sp, idx, _ = rand_coo((4, 5), seed=17)
        d = RNG.randn(4, 5).astype(np.float32)
        out = S.mask_as(P.to_tensor(d), sp)
        np.testing.assert_allclose(_v(out.values()), d[idx[0], idx[1]])

    def test_is_same_shape(self):
        a, _, _ = rand_coo((4, 5))
        b, _, _ = rand_coo((4, 5), seed=20)
        assert S.is_same_shape(a, b)


class TestSparseNN:
    def test_relu(self):
        sp, _, vals = rand_coo(seed=18)
        out = S.nn.ReLU()(sp)
        np.testing.assert_allclose(_v(out.values()), np.maximum(vals, 0))

    def test_softmax_rows(self):
        sp, _, _ = rand_coo((4, 5), nnz=8, seed=19)
        csr = sp.to_sparse_csr()
        out = S.nn.Softmax()(csr)
        dense = _v(sp.to_dense())
        vals = _v(out.to_dense())
        # each nonzero row of the softmax'd values sums to 1
        for r in range(4):
            nz = dense[r] != 0
            if nz.any():
                np.testing.assert_allclose(vals[r][nz].sum(), 1.0, rtol=1e-5)

    def test_batch_norm(self):
        idx = np.stack([np.arange(6) % 2, np.arange(6) % 3, np.zeros(6, int)])
        vals = RNG.randn(6, 4).astype(np.float32)
        sp = S.sparse_coo_tensor(idx, vals, [2, 3, 2, 4])
        bn = S.nn.BatchNorm(4)
        out = bn(sp)
        assert list(_v(out.values()).shape) == [6, 4]

    def test_subm_conv2d_keeps_pattern(self):
        idx = np.array([[0, 0, 0], [1, 2, 3], [1, 2, 3], [0, 0, 0]])[:, :3]
        vals = RNG.randn(3, 2).astype(np.float32)
        sp = S.sparse_coo_tensor(np.array([[0, 0, 0], [1, 2, 0], [1, 2, 3]]),
                                 vals, [1, 4, 4, 2])
        conv = S.nn.SubmConv2D(2, 5, kernel_size=3, padding=1)
        out = conv(sp)
        assert out.nnz == sp.nnz
        assert out.shape[-1] == 5


class TestReviewRegressions:
    def test_conv_pattern_keeps_cancelling_channels(self):
        # a site whose channels sum to zero must stay in the pattern
        import paddle_tpu.sparse.nn  # noqa: F401

        idx = np.array([[0], [1], [1]])
        sp = S.sparse_coo_tensor(idx, np.array([[1.0, 1.0]], np.float32), [1, 3, 3, 2])
        conv = S.nn.Conv2D(2, 2, kernel_size=1, bias_attr=False)
        w = np.zeros((2, 2, 1, 1), np.float32)
        w[0, 0] = 1.0
        w[1, 0] = -1.0  # out channels = [+v, -v] -> sums to 0 at active site
        conv.weight.set_value(w)
        out = conv(sp)
        dense = _v(out.to_dense())
        assert dense[0, 1, 1, 0] == 1.0 and dense[0, 1, 1, 1] == -1.0

    def test_creation_does_not_detach_caller_tensor(self):
        v = P.to_tensor(np.ones(3, np.float32))
        v.stop_gradient = False
        S.sparse_coo_tensor(np.array([[0, 1, 2]]), v, [4])
        assert v.stop_gradient is False

    def test_csr_sum_axis_returns_coo(self):
        csr = S.sparse_csr_tensor([0, 2, 3], [0, 2, 1], [1.0, 2.0, 3.0], [2, 3])
        out = S.sum(csr, axis=0)
        np.testing.assert_allclose(_v(out.to_dense()), _v(csr.to_dense()).sum(0))

    def test_mixed_format_add(self):
        sp, idx, vals = rand_coo(seed=30)
        csr = sp.to_sparse_csr()
        out1 = S.add(csr, sp)
        assert out1.is_sparse_csr
        np.testing.assert_allclose(_v(out1.to_dense()), 2 * _v(sp.to_dense()), rtol=1e-5)
        out2 = S.add(sp, csr)
        assert out2.is_sparse_coo
        np.testing.assert_allclose(_v(out2.to_dense()), 2 * _v(sp.to_dense()), rtol=1e-5)


class TestGatherConvJitSafe:
    """Sparse convs must run under jax.jit (no host
    nonzero / densify on the value path) and match the dense reference."""

    def test_subm_conv_under_jit(self):
        import jax

        idx = np.array([[0, 0, 0, 0], [0, 1, 2, 3], [1, 2, 0, 3]])
        vals = RNG.randn(4, 2).astype(np.float32)
        sp = S.sparse_coo_tensor(idx, vals, [1, 4, 4, 2])
        conv = S.nn.SubmConv2D(2, 5, kernel_size=3, padding=1)
        ref = _v(conv(sp).values())

        def fn(v):
            from paddle_tpu.tensor.tensor import Tensor

            out = conv(S.sparse_coo_tensor(idx, Tensor(v), [1, 4, 4, 2]))
            return out._values._value

        jit_vals = np.asarray(jax.jit(fn)(sp._values._value))
        np.testing.assert_allclose(jit_vals, ref, rtol=1e-5)

    def test_conv_under_jit(self):
        import jax

        idx = np.array([[0, 0], [1, 2], [1, 3]])
        vals = RNG.randn(2, 3).astype(np.float32)
        sp = S.sparse_coo_tensor(idx, vals, [1, 5, 5, 3])
        conv = S.nn.Conv2D(3, 4, kernel_size=3, padding=1)
        ref = _v(conv(sp).values())

        def fn(v):
            from paddle_tpu.tensor.tensor import Tensor

            out = conv(S.sparse_coo_tensor(idx, Tensor(v), [1, 5, 5, 3]))
            return out._values._value

        jit_vals = np.asarray(jax.jit(fn)(sp._values._value))
        np.testing.assert_allclose(jit_vals, ref, rtol=1e-5)

    def test_conv_matches_dense_reference(self):
        # gather-rulebook values == dense conv sampled at the output pattern
        idx = np.array([[0, 0, 0], [0, 2, 4], [1, 3, 0]])
        vals = RNG.randn(3, 2).astype(np.float32)
        sp = S.sparse_coo_tensor(idx, vals, [1, 5, 5, 2])
        conv = S.nn.Conv2D(2, 3, kernel_size=3, stride=2, padding=1)
        out = conv(sp)
        # dense reference via nn.functional.conv2d with the same weights
        dense = np.zeros((1, 5, 5, 2), np.float32)
        dense[tuple(idx)] = vals
        x = P.to_tensor(dense.transpose(0, 3, 1, 2))
        ref = P.nn.functional.conv2d(
            x, conv.weight, conv.bias, stride=2, padding=1)
        ref = np.asarray(ref._value).transpose(0, 2, 3, 1)
        got = np.zeros_like(ref)
        got[tuple(np.asarray(out._indices))] = _v(out.values())
        # every out site in the pattern must match the dense conv there
        oi = np.asarray(out._indices)
        np.testing.assert_allclose(got[tuple(oi)], ref[tuple(oi)],
                                   rtol=1e-4, atol=1e-5)
        # off-pattern sites of the dense ref must be zero (pattern complete)
        mask = np.zeros(ref.shape[:-1], bool)
        mask[tuple(oi)] = True
        np.testing.assert_allclose(ref[~mask], 0.0, atol=1e-5)

    def test_subm_conv3d_matches_dense(self):
        idx = np.array([[0, 0], [1, 2], [0, 3], [2, 1]])
        vals = RNG.randn(2, 2).astype(np.float32)
        sp = S.sparse_coo_tensor(idx, vals, [1, 4, 4, 4, 2])
        conv = S.nn.SubmConv3D(2, 3, kernel_size=3, padding=1)
        out = conv(sp)
        dense = np.zeros((1, 4, 4, 4, 2), np.float32)
        dense[tuple(idx)] = vals
        x = P.to_tensor(dense.transpose(0, 4, 1, 2, 3))
        ref = P.nn.functional.conv3d(x, conv.weight, conv.bias, padding=1)
        ref = np.asarray(ref._value).transpose(0, 2, 3, 4, 1)
        np.testing.assert_allclose(_v(out.values()), ref[tuple(idx)],
                                   rtol=1e-4, atol=1e-5)

    def test_grads_flow_through_gather_conv(self):
        idx = np.array([[0, 0, 0], [1, 2, 3], [1, 2, 3]])
        vals = P.to_tensor(RNG.randn(3, 2).astype(np.float32))
        vals.stop_gradient = False
        sp = S.sparse_coo_tensor(idx, vals, [1, 4, 4, 2])
        conv = S.nn.SubmConv2D(2, 4, kernel_size=3, padding=1)
        out = conv(sp)
        out.values().sum().backward()
        assert conv.weight.grad is not None
        assert np.isfinite(np.asarray(conv.weight.grad._value)).all()
