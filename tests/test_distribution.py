"""paddle.distribution parity tests.

log_prob checked against scipy.stats, KL closed forms against Monte-Carlo
estimates, transforms against round-trip + autodiff log-det, rsample
gradient flow through the tape."""
import math

import numpy as np
import pytest
import scipy.stats as st

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu import distribution as D


def _lp(dist, x):
    return np.asarray(dist.log_prob(P.to_tensor(np.asarray(x, np.float32)))._value)


SCIPY_CASES = [
    ("Normal", lambda: D.Normal(0.5, 2.0), lambda x: st.norm.logpdf(x, 0.5, 2.0), np.linspace(-4, 4, 7)),
    ("Uniform", lambda: D.Uniform(-1.0, 3.0), lambda x: st.uniform.logpdf(x, -1, 4), np.linspace(-0.5, 2.5, 5)),
    ("Laplace", lambda: D.Laplace(0.0, 1.5), lambda x: st.laplace.logpdf(x, 0, 1.5), np.linspace(-3, 3, 5)),
    ("Gumbel", lambda: D.Gumbel(1.0, 2.0), lambda x: st.gumbel_r.logpdf(x, 1, 2), np.linspace(-2, 6, 5)),
    ("Cauchy", lambda: D.Cauchy(0.0, 1.0), lambda x: st.cauchy.logpdf(x), np.linspace(-3, 3, 5)),
    ("Exponential", lambda: D.Exponential(1.7), lambda x: st.expon.logpdf(x, scale=1/1.7), np.linspace(0.1, 3, 5)),
    ("Gamma", lambda: D.Gamma(2.5, 1.3), lambda x: st.gamma.logpdf(x, 2.5, scale=1/1.3), np.linspace(0.2, 4, 5)),
    ("Beta", lambda: D.Beta(2.0, 3.0), lambda x: st.beta.logpdf(x, 2, 3), np.linspace(0.1, 0.9, 5)),
    ("LogNormal", lambda: D.LogNormal(0.3, 0.8), lambda x: st.lognorm.logpdf(x, 0.8, scale=math.exp(0.3)), np.linspace(0.2, 4, 5)),
    ("Chi2", lambda: D.Chi2(3.0), lambda x: st.chi2.logpdf(x, 3), np.linspace(0.5, 6, 5)),
    ("StudentT", lambda: D.StudentT(4.0, 0.5, 2.0), lambda x: st.t.logpdf(x, 4, 0.5, 2.0), np.linspace(-3, 4, 5)),
    ("Poisson", lambda: D.Poisson(2.5), lambda x: st.poisson.logpmf(x, 2.5), np.arange(0, 6, dtype=np.float32)),
    ("Bernoulli", lambda: D.Bernoulli(probs=0.3), lambda x: st.bernoulli.logpmf(x, 0.3), np.array([0.0, 1.0])),
    ("Geometric", lambda: D.Geometric(0.4), lambda x: st.geom.logpmf(x + 1, 0.4), np.arange(0, 5, dtype=np.float32)),
    ("Binomial", lambda: D.Binomial(10.0, 0.35), lambda x: st.binom.logpmf(x, 10, 0.35), np.arange(0, 10, 2, dtype=np.float32)),
]


class TestLogProbVsScipy:
    @pytest.mark.parametrize("name,mk,ref,xs", SCIPY_CASES, ids=[c[0] for c in SCIPY_CASES])
    def test_matches(self, name, mk, ref, xs):
        np.testing.assert_allclose(_lp(mk(), xs), ref(xs), rtol=2e-4, atol=2e-5)

    @pytest.mark.quick
    def test_categorical(self):
        logits = np.array([0.1, 1.2, -0.5], np.float32)
        d = D.Categorical(logits=logits)
        expect = logits - np.log(np.exp(logits).sum())
        got = np.asarray(d.log_prob(P.to_tensor(np.array([0, 1, 2])))._value)
        np.testing.assert_allclose(got, expect, rtol=1e-5)

    def test_dirichlet(self):
        c = np.array([1.5, 2.0, 3.0], np.float32)
        d = D.Dirichlet(c)
        x = np.array([0.2, 0.3, 0.5], np.float32)
        np.testing.assert_allclose(_lp(d, x), st.dirichlet.logpdf(x, c), rtol=1e-4)

    def test_multinomial(self):
        d = D.Multinomial(6, np.array([0.2, 0.3, 0.5], np.float32))
        x = np.array([1.0, 2.0, 3.0], np.float32)
        np.testing.assert_allclose(_lp(d, x), st.multinomial.logpmf(x, 6, [0.2, 0.3, 0.5]),
                                   rtol=1e-4)

    def test_multivariate_normal(self):
        mu = np.array([0.5, -1.0], np.float32)
        cov = np.array([[2.0, 0.5], [0.5, 1.0]], np.float32)
        d = D.MultivariateNormal(mu, covariance_matrix=cov)
        x = np.array([0.3, 0.2], np.float32)
        np.testing.assert_allclose(_lp(d, x), st.multivariate_normal.logpdf(x, mu, cov),
                                   rtol=1e-4)


class TestMomentsAndSampling:
    @pytest.mark.parametrize("mk,mean,var", [
        (lambda: D.Normal(1.0, 2.0), 1.0, 4.0),
        (lambda: D.Exponential(2.0), 0.5, 0.25),
        (lambda: D.Beta(2.0, 2.0), 0.5, 1.0 / 20),
        (lambda: D.Gamma(3.0, 2.0), 1.5, 0.75),
        (lambda: D.Laplace(0.0, 1.0), 0.0, 2.0),
        (lambda: D.Uniform(0.0, 2.0), 1.0, 4.0 / 12),
    ])
    def test_sample_moments(self, mk, mean, var):
        P.seed(0)
        d = mk()
        s = np.asarray(d.sample([20000])._value)
        assert abs(s.mean() - mean) < 0.08
        assert abs(s.var() - var) < 0.15
        np.testing.assert_allclose(float(d.mean._value), mean, rtol=1e-5)
        np.testing.assert_allclose(float(d.variance._value), var, rtol=1e-5)

    def test_entropy_normal(self):
        d = D.Normal(0.0, 2.0)
        np.testing.assert_allclose(float(d.entropy()._value), st.norm.entropy(0, 2), rtol=1e-5)

    def test_rsample_gradient_flows(self):
        loc = P.to_tensor(np.float32(0.0))
        loc.stop_gradient = False
        scale = P.to_tensor(np.float32(1.0))
        scale.stop_gradient = False
        P.seed(1)
        s = D.Normal(loc, scale).rsample([256])
        s.sum().backward()
        assert loc.grad is not None and abs(float(loc.grad._value) - 256.0) < 1e-3
        assert scale.grad is not None


class TestKL:
    @pytest.mark.parametrize("p,q", [
        (lambda: D.Normal(0.0, 1.0), lambda: D.Normal(1.0, 2.0)),
        (lambda: D.Exponential(1.0), lambda: D.Exponential(2.5)),
        (lambda: D.Gamma(2.0, 1.0), lambda: D.Gamma(3.0, 2.0)),
        (lambda: D.Beta(2.0, 2.0), lambda: D.Beta(3.0, 1.5)),
        (lambda: D.Laplace(0.0, 1.0), lambda: D.Laplace(0.5, 2.0)),
        (lambda: D.Bernoulli(probs=0.3), lambda: D.Bernoulli(probs=0.6)),
        (lambda: D.Poisson(2.0), lambda: D.Poisson(3.5)),
    ])
    def test_closed_form_vs_monte_carlo(self, p, q):
        P.seed(3)
        dp, dq = p(), q()
        kl = float(D.kl_divergence(dp, dq)._value)
        s = dp.sample([200000])
        mc = float((dp.log_prob(s) - dq.log_prob(s)).mean()._value)
        assert abs(kl - mc) < max(0.05, 0.1 * abs(kl)), (kl, mc)

    def test_categorical_kl(self):
        p = D.Categorical(logits=np.array([0.0, 1.0, 2.0], np.float32))
        q = D.Categorical(logits=np.array([1.0, 1.0, 1.0], np.float32))
        pp = np.exp([0, 1, 2]) / np.exp([0, 1, 2]).sum()
        expect = float((pp * np.log(pp / (np.ones(3) / 3))).sum())
        np.testing.assert_allclose(float(D.kl_divergence(p, q)._value), expect, rtol=1e-5)

    def test_unregistered_raises(self):
        with pytest.raises(NotImplementedError):
            D.kl_divergence(D.Cauchy(0.0, 1.0), D.Normal(0.0, 1.0))


class TestTransforms:
    @pytest.mark.parametrize("t,x", [
        (D.ExpTransform(), 0.7), (D.SigmoidTransform(), 0.3),
        (D.TanhTransform(), 0.4), (D.AffineTransform(1.0, 3.0), 0.9),
        (D.PowerTransform(2.0), 1.3),
    ])
    def test_roundtrip_and_logdet(self, t, x):
        xv = P.to_tensor(np.float32(x))
        y = t.forward(xv)
        back = t.inverse(y)
        np.testing.assert_allclose(float(back._value), x, rtol=1e-5)
        # log|dy/dx| via jax autodiff
        g = jax.grad(lambda v: t._forward(v))(jnp.float32(x))
        np.testing.assert_allclose(float(t.forward_log_det_jacobian(xv)._value),
                                   math.log(abs(float(g))), rtol=1e-4)

    def test_chain(self):
        chain = D.ChainTransform([D.AffineTransform(0.0, 2.0), D.ExpTransform()])
        x = P.to_tensor(np.float32(0.5))
        y = chain.forward(x)
        np.testing.assert_allclose(float(y._value), math.exp(1.0), rtol=1e-5)
        np.testing.assert_allclose(float(chain.inverse(y)._value), 0.5, rtol=1e-5)

    def test_stickbreaking_simplex(self):
        t = D.StickBreakingTransform()
        x = P.to_tensor(np.array([0.2, -0.3, 0.5], np.float32))
        y = t.forward(x)
        s = np.asarray(y._value)
        assert s.shape == (4,)
        np.testing.assert_allclose(s.sum(), 1.0, rtol=1e-5)
        back = t.inverse(y)
        np.testing.assert_allclose(np.asarray(back._value), np.asarray(x._value),
                                   rtol=1e-4, atol=1e-5)

    def test_transformed_distribution_lognormal(self):
        P.seed(7)
        base = D.Normal(0.3, 0.8)
        td = D.TransformedDistribution(base, [D.ExpTransform()])
        ref = D.LogNormal(0.3, 0.8)
        xs = P.to_tensor(np.linspace(0.3, 3.0, 5).astype(np.float32))
        np.testing.assert_allclose(np.asarray(td.log_prob(xs)._value),
                                   np.asarray(ref.log_prob(xs)._value), rtol=1e-4)

    def test_independent(self):
        d = D.Independent(D.Normal(np.zeros((3, 4), np.float32), 1.0), 1)
        assert d.batch_shape == (3,)
        assert d.event_shape == (4,)
        lp = d.log_prob(P.to_tensor(np.zeros((3, 4), np.float32)))
        assert lp.shape == [3]
        np.testing.assert_allclose(np.asarray(lp._value),
                                   4 * st.norm.logpdf(0.0) * np.ones(3), rtol=1e-5)

    def test_transformed_distribution_param_grad(self):
        """Gradients must reach the base distribution's parameters through
        TransformedDistribution.log_prob (review regression)."""
        loc = P.to_tensor(np.float32(0.3))
        loc.stop_gradient = False
        td = D.TransformedDistribution(D.Normal(loc, 1.0), [D.ExpTransform()])
        lp = td.log_prob(P.to_tensor(np.float32(2.0)))
        lp.backward()
        assert loc.grad is not None
        np.testing.assert_allclose(
            float(np.asarray(loc.grad._value)), float(np.log(2.0) - 0.3), rtol=1e-5)

    def test_binomial_kl_mismatched_counts(self):
        # p wider than q: support not nested -> +inf
        kl = D.kl_divergence(D.Binomial(20.0, 0.3), D.Binomial(10.0, 0.3))
        assert np.isinf(float(np.asarray(kl._value)))
        # p narrower than q: finite but not implemented -> loud failure
        with pytest.raises(NotImplementedError):
            D.kl_divergence(D.Binomial(10.0, 0.3), D.Binomial(20.0, 0.3))
        kl2 = D.kl_divergence(D.Binomial(10.0, 0.3), D.Binomial(10.0, 0.4))
        v = float(np.asarray(kl2._value))
        assert np.isfinite(v) and v > 0

    def test_categorical_scalar_value_batched_logits(self):
        d = D.Categorical(logits=np.ones((2, 3), np.float32))
        lp = d.log_prob(P.to_tensor(np.float32(1.0)))
        assert tuple(lp.shape) == (2,)
        np.testing.assert_allclose(np.asarray(lp._value), np.log(1 / 3) * np.ones(2), rtol=1e-5)

    def test_transform_param_grad(self):
        loc = P.to_tensor(np.float32(1.0))
        loc.stop_gradient = False
        td = D.TransformedDistribution(D.Normal(0.0, 1.0), [D.AffineTransform(loc, 2.0)])
        td.log_prob(P.to_tensor(np.float32(2.0))).backward()
        assert loc.grad is not None
        np.testing.assert_allclose(float(np.asarray(loc.grad._value)), 0.25, rtol=1e-5)

    def test_nested_base_param_grad(self):
        """Params of a nested Independent base must get grads (review regression)."""
        loc = P.to_tensor(np.array([0.3, 0.1], np.float32))
        loc.stop_gradient = False
        td = D.TransformedDistribution(
            D.Independent(D.Normal(loc, 1.0), 1), [D.ExpTransform()])
        td.log_prob(P.to_tensor(np.array([2.0, 1.0], np.float32))).backward()
        assert loc.grad is not None
        np.testing.assert_allclose(
            np.asarray(loc.grad._value),
            np.log([2.0, 1.0]) - np.array([0.3, 0.1]), rtol=1e-4, atol=1e-5)
