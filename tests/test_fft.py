"""paddle.fft parity tests: values vs numpy.fft,
gradients vs finite differences / known identities."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import fft as F


def _v(t):
    return np.asarray(t._value)


RNG = np.random.RandomState(42)
X1 = RNG.randn(8).astype(np.float32)
X2 = RNG.randn(4, 6).astype(np.float32)
C1 = (RNG.randn(8) + 1j * RNG.randn(8)).astype(np.complex64)


class TestValuesVsNumpy:
    @pytest.mark.parametrize("norm", ["backward", "forward", "ortho"])
    def test_fft_ifft(self, norm):
        np.testing.assert_allclose(_v(F.fft(C1, norm=norm)), np.fft.fft(C1, norm=norm), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(_v(F.ifft(C1, norm=norm)), np.fft.ifft(C1, norm=norm), rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("norm", ["backward", "forward", "ortho"])
    def test_rfft_irfft(self, norm):
        r = F.rfft(X1, norm=norm)
        np.testing.assert_allclose(_v(r), np.fft.rfft(X1, norm=norm), rtol=1e-4, atol=1e-5)
        back = F.irfft(r, n=8, norm=norm)
        np.testing.assert_allclose(_v(back), X1, rtol=1e-4, atol=1e-5)

    @pytest.mark.quick
    def test_hfft_ihfft(self):
        h = np.fft.ihfft(X1)
        np.testing.assert_allclose(_v(F.ihfft(X1)), h, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(_v(F.hfft(h, n=8)), np.fft.hfft(h, n=8), rtol=1e-4, atol=1e-4)

    def test_fft2_roundtrip(self):
        y = F.fft2(X2)
        np.testing.assert_allclose(_v(y), np.fft.fft2(X2), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(_v(F.ifft2(y)).real, X2, rtol=1e-4, atol=1e-5)

    def test_fftn_axes_s(self):
        y = F.fftn(X2, s=(8, 4), axes=(0, 1))
        np.testing.assert_allclose(_v(y), np.fft.fftn(X2, s=(8, 4), axes=(0, 1)),
                                   rtol=1e-4, atol=1e-4)

    def test_rfft2_irfft2(self):
        y = F.rfft2(X2)
        np.testing.assert_allclose(_v(y), np.fft.rfft2(X2), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(_v(F.irfft2(y, s=X2.shape)), X2, rtol=1e-4, atol=1e-5)

    def test_freq_shift_helpers(self):
        np.testing.assert_allclose(_v(F.fftfreq(10, d=0.5)), np.fft.fftfreq(10, 0.5), rtol=1e-6)
        np.testing.assert_allclose(_v(F.rfftfreq(10, d=0.5)), np.fft.rfftfreq(10, 0.5), rtol=1e-6)
        a = np.arange(10, dtype=np.float32)
        np.testing.assert_allclose(_v(F.fftshift(a)), np.fft.fftshift(a))
        np.testing.assert_allclose(_v(F.ifftshift(a)), np.fft.ifftshift(a))

    def test_bad_norm_raises(self):
        with pytest.raises(ValueError):
            F.fft(X1, norm="bogus")


class TestGradients:
    def test_rfft_energy_grad(self):
        # Parseval: d/dx of sum|rfft(x)|^2 — check vs finite differences
        x = P.to_tensor(X1.copy())
        x.stop_gradient = False
        y = F.rfft(x)
        energy = P.sum(P.real(y * P.conj(y))) if hasattr(P, "conj") else P.sum(P.abs(y) ** 2)
        energy.backward()
        g = _v(x.grad)
        eps = 1e-3
        num = np.zeros_like(X1)
        for i in range(X1.size):
            xp, xm = X1.copy(), X1.copy()
            xp[i] += eps
            xm[i] -= eps
            num[i] = (np.abs(np.fft.rfft(xp)) ** 2).sum() - (np.abs(np.fft.rfft(xm)) ** 2).sum()
            num[i] /= 2 * eps
        np.testing.assert_allclose(g, num, rtol=1e-2, atol=1e-2)

    def test_irfft_grad_flows(self):
        x = P.to_tensor(X1.copy())
        x.stop_gradient = False
        out = F.irfft(F.rfft(x), n=8)
        P.sum(out).backward()
        # roundtrip is identity, so grad of sum is all ones
        np.testing.assert_allclose(_v(x.grad), np.ones(8), rtol=1e-4, atol=1e-5)


class TestHermitianND:
    """hfftn/ihfftn/hfft2/ihfft2 vs scipy.fft (review regression)."""

    @pytest.mark.parametrize("norm", ["backward", "forward", "ortho"])
    def test_hfft2_vs_scipy(self, norm):
        import scipy.fft as sfft

        c = (RNG.randn(4, 6) + 1j * RNG.randn(4, 6)).astype(np.complex64)
        np.testing.assert_allclose(_v(F.hfft2(c, norm=norm)), sfft.hfft2(c, norm=norm),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("norm", ["backward", "forward", "ortho"])
    def test_ihfft2_vs_scipy(self, norm):
        import scipy.fft as sfft

        np.testing.assert_allclose(_v(F.ihfft2(X2, norm=norm)), sfft.ihfft2(X2, norm=norm),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("norm", ["backward", "forward", "ortho"])
    def test_hfftn_ihfftn_vs_scipy(self, norm):
        import scipy.fft as sfft

        c = (RNG.randn(3, 4, 5) + 1j * RNG.randn(3, 4, 5)).astype(np.complex64)
        np.testing.assert_allclose(_v(F.hfftn(c, norm=norm)), sfft.hfftn(c, norm=norm),
                                   rtol=1e-3, atol=1e-3)
        r = RNG.randn(3, 4, 5).astype(np.float32)
        np.testing.assert_allclose(_v(F.ihfftn(r, norm=norm)), sfft.ihfftn(r, norm=norm),
                                   rtol=1e-4, atol=1e-5)
