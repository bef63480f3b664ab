"""quantization (QAT/PTQ/weight-only int8) + inference Predictor tests
(quantization and the load-and-run inference path)."""
import os

import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu import inference, quantization as Q


RNG = np.random.RandomState(5)


def small_net():
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    return net


class TestFakeQuant:
    def test_ste_gradient_passes_through(self):
        q = Q.FakeQuanterWithAbsMaxObserver()
        x = P.to_tensor(RNG.randn(4, 4).astype(np.float32))
        x.stop_gradient = False
        out = q(x)
        P.sum(out).backward()
        np.testing.assert_allclose(np.asarray(x.grad._value), np.ones((4, 4)), rtol=1e-6)

    @pytest.mark.quick
    def test_quant_error_small(self):
        q = Q.FakeQuanterWithAbsMaxObserver()
        x = P.to_tensor(RNG.randn(32).astype(np.float32))
        out = q(x)
        err = np.abs(np.asarray(out._value) - np.asarray(x._value)).max()
        assert err < np.abs(np.asarray(x._value)).max() / 100  # 8-bit → <1% of range

    def test_absmax_observer(self):
        ob = Q.AbsmaxObserver()
        ob(P.to_tensor(np.array([1.0, -3.0], np.float32)))
        ob(P.to_tensor(np.array([2.0, 0.5], np.float32)))
        np.testing.assert_allclose(ob.scales(), 3.0 / 127, rtol=1e-6)


class TestQATPTQ:
    def test_qat_wraps_and_trains(self):
        net = small_net()
        cfg = Q.QuantConfig(activation=Q.FakeQuanterWithAbsMaxObserver(),
                            weight=Q.FakeQuanterWithAbsMaxObserver())
        qnet = Q.QAT(cfg).quantize(net)
        assert isinstance(qnet[0], Q.QuantedLinear)
        opt = P.optimizer.Adam(parameters=qnet.parameters(), learning_rate=0.01)
        x = P.to_tensor(RNG.randn(16, 8).astype(np.float32))
        y = P.to_tensor(RNG.randn(16, 4).astype(np.float32))
        losses = []
        for _ in range(20):
            loss = P.mean((qnet(x) - y) ** 2)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss._value))
        assert losses[-1] < losses[0]

    def test_ptq_calibrate_convert(self):
        net = small_net()
        cfg = Q.QuantConfig(activation=None, weight=Q.FakeQuanterWithAbsMaxObserver())
        ptq = Q.PTQ(cfg)
        qnet = ptq.quantize(net)
        for _ in range(4):
            qnet(P.to_tensor(RNG.randn(8, 8).astype(np.float32)))
        final = ptq.convert(qnet)
        assert isinstance(final[0], nn.Linear)
        x = P.to_tensor(RNG.randn(4, 8).astype(np.float32))
        a = np.asarray(net(x)._value)
        b = np.asarray(final(x)._value)
        assert np.abs(a - b).max() < 0.2  # quantized weights ≈ original


class TestWeightOnly:
    def test_quant_dequant_roundtrip(self):
        w = P.to_tensor(RNG.randn(8, 16).astype(np.float32))
        qw, scale = Q.weight_quantize(w)
        assert str(qw._value.dtype) == "int8"
        back = np.asarray(Q.weight_dequantize(qw, scale)._value)
        assert np.abs(back - np.asarray(w._value)).max() < np.abs(np.asarray(w._value)).max() / 50

    def test_unrecognized_algo_raises(self):
        """An unknown algo (e.g. 'weight_only_int4')
        must raise instead of silently falling through to int8 with a
        mislabelled result."""
        w = P.to_tensor(np.random.RandomState(0).randn(8, 16).astype(np.float32))
        with pytest.raises(ValueError, match="weight_only_int4"):
            Q.weight_quantize(w, algo="weight_only_int4")

    def test_weight_only_linear_matches(self):
        w = P.to_tensor(RNG.randn(8, 16).astype(np.float32))
        x = P.to_tensor(RNG.randn(4, 8).astype(np.float32))
        b = P.to_tensor(RNG.randn(16).astype(np.float32))
        qw, scale = Q.weight_quantize(w)
        out = np.asarray(Q.weight_only_linear(x, qw, b, scale)._value)
        ref = np.asarray(x._value) @ np.asarray(w._value) + np.asarray(b._value)
        np.testing.assert_allclose(out, ref, rtol=0.05, atol=0.05)


class TestPredictor:
    def test_layer_predictor(self):
        net = small_net()
        cfg = inference.Config()
        cfg.set_layer(net)
        pred = inference.create_predictor(cfg)
        x = RNG.randn(4, 8).astype(np.float32)
        (out,) = pred.run([x])
        ref = np.asarray(net(P.to_tensor(x))._value)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
        # second call hits the shape cache
        pred.run([x])
        assert len(pred._cache) == 1

    def test_weight_only_int8_predictor(self):
        net = small_net()
        cfg = inference.Config()
        cfg.set_layer(net)
        cfg.enable_weight_only_quant("int8")
        pred = inference.create_predictor(cfg)
        x = RNG.randn(4, 8).astype(np.float32)
        (out,) = pred.run([x])
        ref = np.asarray(net(P.to_tensor(x))._value)
        assert np.abs(out - ref).max() < 0.3  # int8 weights ≈ fp32

    def test_saved_artifact_load_and_run(self, tmp_path):
        net = small_net()
        net.eval()
        path = os.path.join(str(tmp_path), "model")
        spec = [P.to_tensor(np.zeros((4, 8), np.float32))]
        P.jit.save(P.jit.to_static(net), path, input_spec=spec)
        assert os.path.exists(path + ".jaxexport")

        cfg = inference.Config(path)
        pred = inference.create_predictor(cfg)
        x = RNG.randn(4, 8).astype(np.float32)
        (out,) = pred.run([x])
        ref = np.asarray(net(P.to_tensor(x))._value)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_handles_api(self):
        net = small_net()
        cfg = inference.Config()
        cfg.set_layer(net)
        pred = inference.create_predictor(cfg)
        h = pred.get_input_handle("x0")
        x = RNG.randn(2, 8).astype(np.float32)
        h.copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle("out0").copy_to_cpu()
        ref = np.asarray(net(P.to_tensor(x))._value)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_batch_padding(self, tmp_path):
        net = small_net()
        net.eval()
        path = os.path.join(str(tmp_path), "model")
        P.jit.save(P.jit.to_static(net), path,
                   input_spec=[P.to_tensor(np.zeros((8, 8), np.float32))])
        cfg = inference.Config(path)
        cfg.enable_batch_padding()
        pred = inference.create_predictor(cfg)
        x = RNG.randn(3, 8).astype(np.float32)  # smaller than compiled batch 8
        (out,) = pred.run([x])
        assert out.shape == (3, 4)
        ref = np.asarray(net(P.to_tensor(x))._value)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


class TestQuantConv:
    def test_qat_conv2d(self):
        conv_net = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.ReLU())
        cfg = Q.QuantConfig(activation=None, weight=Q.FakeQuanterWithAbsMaxObserver())
        qnet = Q.QAT(cfg).quantize(conv_net)
        assert isinstance(qnet[0], Q.QuantedConv2D)
        x = P.to_tensor(RNG.randn(2, 3, 8, 8).astype(np.float32))
        out = qnet(x)
        assert list(out.shape) == [2, 8, 8, 8]
        # gradients flow to the (copied) conv weight through the fake-quant STE
        P.sum(out).backward()
        assert qnet[0].weight.grad is not None

    def test_convert_with_groupwise_observer(self):
        net = small_net()
        cfg = Q.QuantConfig(activation=None, weight=Q.GroupWiseWeightObserver())
        ptq = Q.PTQ(cfg)
        qnet = ptq.quantize(net)
        qnet(P.to_tensor(RNG.randn(4, 8).astype(np.float32)))
        final = ptq.convert(qnet)
        assert isinstance(final[0], nn.Linear)


class TestWeightOnlyFp8:
    """e4m3 weight-only tier (reference fp8_gemm analog)."""

    def test_fp8_quant_dequant_roundtrip(self):
        w = P.to_tensor(RNG.randn(8, 16).astype(np.float32))
        qw, scale = Q.weight_quantize(w, algo="weight_only_fp8")
        assert "float8_e4m3" in str(qw._value.dtype)
        back = np.asarray(Q.weight_dequantize(qw, scale)._value)
        # e4m3 has ~2 decimal digits: fp8 roundtrip must be tighter than 10%
        err = np.abs(back - np.asarray(w._value)).max()
        assert err < np.abs(np.asarray(w._value)).max() * 0.1

    def test_fp8_weight_only_linear_matches(self):
        # a stream of its own: on the module's, what this test draws depends on
        # which tests of the file the same xdist worker ran before it, and one
        # draw in a few lands a single element past the tolerance
        rng = np.random.RandomState(5)
        w = P.to_tensor(rng.randn(8, 16).astype(np.float32))
        x = P.to_tensor(rng.randn(4, 8).astype(np.float32))
        b = P.to_tensor(rng.randn(16).astype(np.float32))
        qw, scale = Q.weight_quantize(w, algo="weight_only_fp8")
        out = np.asarray(Q.weight_only_linear(x, qw, b, scale,
                                              weight_dtype="fp8")._value)
        ref = np.asarray(x._value) @ np.asarray(w._value) + np.asarray(b._value)
        np.testing.assert_allclose(out, ref, rtol=0.08, atol=0.08)

    def test_fp8_more_accurate_than_int8_on_outliers(self):
        # fp8's exponent handles heavy-tailed rows better than linear int8
        wv = RNG.randn(16, 8).astype(np.float32)
        wv[0] *= 100.0  # one outlier row blows up the int8 scale
        w = P.to_tensor(wv)
        q8, s8 = Q.weight_quantize(w)
        qf, sf = Q.weight_quantize(w, algo="weight_only_fp8")
        b8 = np.asarray(Q.weight_dequantize(q8, s8)._value)
        bf = np.asarray(Q.weight_dequantize(qf, sf)._value)
        small = np.abs(wv) < 1.0
        err8 = np.abs(b8 - wv)[small].mean()
        errf = np.abs(bf - wv)[small].mean()
        assert errf < err8

    def test_fp8_under_jit(self):
        import jax

        w = P.to_tensor(RNG.randn(8, 16).astype(np.float32))
        qw, scale = Q.weight_quantize(w, algo="weight_only_fp8")

        def fn(xv):
            from paddle_tpu.tensor.tensor import Tensor

            return Q.weight_only_linear(Tensor(xv), qw, None, scale,
                                        weight_dtype="fp8")._value

        x = RNG.randn(4, 8).astype(np.float32)
        out = np.asarray(jax.jit(fn)(x))
        ref = x @ np.asarray(w._value)
        # jit-safety check; e4m3 carries ~6% per-element error
        np.testing.assert_allclose(out, ref, rtol=0.2, atol=0.2)
