"""OpTest-style numeric gradient harness.

The reference checks every op's analytic gradient against central finite
differences (/root/reference/test/legacy_test/op_test.py:148
get_numeric_gradient / :3109 check_grad). This module applies that
discipline across the op surface in one parametrized table: >=100 ops,
each checked analytic-vs-numeric on a small tensor in a domain where the
op is differentiable.
"""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn.functional as F

RNG = np.random.RandomState(7)


def numeric_grad(fn, x, eps=1e-3):
    g = np.zeros_like(x, dtype=np.float64)
    flat = x.reshape(-1)
    gf = g.reshape(-1)
    for i in range(flat.size):
        orig = flat[i]
        flat[i] = orig + eps
        f1 = fn(x)
        flat[i] = orig - eps
        f2 = fn(x)
        flat[i] = orig
        gf[i] = (f1 - f2) / (2 * eps)
    return g


def check(op, x_np, rtol=2e-2, atol=2e-3):
    x = P.to_tensor(x_np.astype(np.float32), stop_gradient=False)
    P.sum(op(x)).backward()
    analytic = x.grad.numpy().astype(np.float64)

    def f(a):
        return float(P.sum(op(P.to_tensor(a.astype(np.float32)))).numpy())

    numeric = numeric_grad(f, x_np.astype(np.float64).copy())
    np.testing.assert_allclose(analytic, numeric, rtol=rtol, atol=atol)


# domain -> concrete sample
def _dom(d, shape=(3, 4)):
    if d == "any":
        return RNG.randn(*shape)
    if d == "pos":
        return RNG.rand(*shape) + 0.5
    if d == "unit":
        return RNG.rand(*shape) * 0.8 + 0.1  # (0.1, 0.9)
    if d == "gt1":
        return RNG.rand(*shape) + 1.1
    if d == "sym1":
        return RNG.rand(*shape) * 1.6 - 0.8  # (-0.8, 0.8)
    if d == "small":
        return RNG.randn(*shape) * 0.3
    raise ValueError(d)


W34 = P.to_tensor(RNG.randn(4, 5).astype(np.float32))
V4 = P.to_tensor(RNG.randn(4).astype(np.float32))
M33 = P.to_tensor(RNG.randn(3, 3).astype(np.float32))
IDX = P.to_tensor(np.array([0, 2, 1], np.int64))

# (name, op, domain) — op: Tensor -> Tensor (any shape)
UNARY = [
    ("exp", lambda t: P.exp(t), "any"),
    ("expm1", lambda t: P.expm1(t), "any"),
    ("log", lambda t: P.log(t), "pos"),
    ("log1p", lambda t: P.log1p(t), "pos"),
    ("log2", lambda t: P.log2(t), "pos"),
    ("log10", lambda t: P.log10(t), "pos"),
    ("sqrt", lambda t: P.sqrt(t), "pos"),
    ("rsqrt", lambda t: P.rsqrt(t), "pos"),
    ("abs", lambda t: P.abs(t), "pos"),
    ("square", lambda t: P.square(t), "any"),
    ("reciprocal", lambda t: P.reciprocal(t), "pos"),
    ("sin", lambda t: P.sin(t), "any"),
    ("cos", lambda t: P.cos(t), "any"),
    ("tan", lambda t: P.tan(t), "sym1"),
    ("asin", lambda t: P.asin(t), "sym1"),
    ("acos", lambda t: P.acos(t), "sym1"),
    ("atan", lambda t: P.atan(t), "any"),
    ("sinh", lambda t: P.sinh(t), "any"),
    ("cosh", lambda t: P.cosh(t), "any"),
    ("tanh", lambda t: P.tanh(t), "any"),
    ("asinh", lambda t: P.asinh(t), "any"),
    ("acosh", lambda t: P.acosh(t), "gt1"),
    ("atanh", lambda t: P.atanh(t), "sym1"),
    ("erf", lambda t: P.erf(t), "any"),
    ("erfinv", lambda t: P.erfinv(t), "sym1"),
    ("sigmoid", lambda t: F.sigmoid(t), "any"),
    ("logit", lambda t: P.logit(t), "unit"),
    ("stanh", lambda t: P.stanh(t), "any"),
    ("exponential_pow", lambda t: t ** 2.5, "pos"),
    ("rpow", lambda t: 2.0 ** t, "any"),
    ("neg", lambda t: -t, "any"),
    ("digamma", lambda t: P.digamma(t), "gt1"),
    ("lgamma", lambda t: P.lgamma(t), "gt1"),
    ("sinc", lambda t: P.sinc(t), "pos"),
    ("trunc_smoothstep", lambda t: t * t * (3 - 2 * t), "unit"),
    ("nan_to_num", lambda t: P.nan_to_num(t), "any"),
    ("clip", lambda t: P.clip(t, -0.5, 0.5), "small"),
    ("scale", lambda t: P.scale(t, scale=3.0, bias=1.0), "any"),
]

BINARY = [
    ("add", lambda t: t + V4, "any"),
    ("subtract", lambda t: t - V4, "any"),
    ("multiply", lambda t: t * V4, "any"),
    ("divide", lambda t: t / P.abs(V4 + 3.0), "any"),
    ("pow_t", lambda t: P.pow(t, 3.0), "pos"),
    ("maximum", lambda t: P.maximum(t, V4), "any"),
    ("minimum", lambda t: P.minimum(t, V4), "any"),
    ("atan2", lambda t: P.atan2(t, P.abs(V4) + 1.0), "pos"),
    ("logaddexp", lambda t: P.logaddexp(t, V4), "any"),
    ("hypot", lambda t: P.hypot(t, P.abs(V4) + 0.5), "pos"),
    ("fmax", lambda t: P.fmax(t, V4), "any"),
    ("fmin", lambda t: P.fmin(t, V4), "any"),
    ("lerp", lambda t: P.lerp(t, V4, 0.3), "any"),
    ("mod_smooth", lambda t: t - 2.0 * (t / 2.0), "pos"),
]

REDUCE = [
    ("sum", lambda t: P.sum(t), "any"),
    ("sum_axis", lambda t: P.sum(t, axis=1), "any"),
    ("mean", lambda t: P.mean(t), "any"),
    ("mean_axis", lambda t: P.mean(t, axis=0), "any"),
    ("max", lambda t: P.max(t, axis=1), "any"),
    ("min", lambda t: P.min(t, axis=0), "any"),
    ("amax", lambda t: P.amax(t, axis=1), "any"),
    ("amin", lambda t: P.amin(t, axis=1), "any"),
    ("prod", lambda t: P.prod(t, axis=1), "pos"),
    ("logsumexp", lambda t: P.logsumexp(t), "any"),
    ("logsumexp_axis", lambda t: P.logsumexp(t, axis=1), "any"),
    ("nansum", lambda t: P.nansum(t), "any"),
    ("nanmean", lambda t: P.nanmean(t), "any"),
    ("std", lambda t: P.std(t), "any"),
    ("var", lambda t: P.var(t), "any"),
    ("cumsum", lambda t: P.cumsum(t, axis=1), "any"),
    ("cumprod", lambda t: P.cumprod(t, dim=1), "pos"),
    ("logcumsumexp", lambda t: P.logcumsumexp(t, axis=1), "any"),
    ("trace", lambda t: P.trace(t), "any"),
    ("diagonal", lambda t: P.diagonal(t), "any"),
    ("diff", lambda t: P.diff(t, axis=1), "any"),
    ("quantile", lambda t: P.quantile(t, 0.5, axis=1), "any"),
]

MATMUL = [
    ("matmul", lambda t: P.matmul(t, W34), "any"),
    ("matmul_tx", lambda t: P.matmul(t, t, transpose_x=True), "any"),
    ("mm", lambda t: P.mm(t, W34), "any"),
    ("bmm", lambda t: P.bmm(t.reshape([1, 3, 4]), W34.reshape([1, 4, 5])), "any"),
    ("dot", lambda t: P.dot(t, P.ones_like(t)), "any"),
    ("inner", lambda t: P.inner(t, W34.T), "any"),
    ("outer", lambda t: P.outer(t, V4), "any"),
    ("kron", lambda t: P.kron(t, M33), "any"),
    ("addmm", lambda t: P.addmm(P.zeros([3, 5]), t, W34), "any"),
    ("vecdot", lambda t: P.linalg.vecdot(t, t + 1.0), "any"),
    ("tensordot", lambda t: P.tensordot(t, W34, axes=1), "any"),
    ("multi_dot", lambda t: P.linalg.multi_dot([t, W34]), "any"),
]

MANIP = [
    ("reshape", lambda t: P.reshape(t, [4, 3]) * 2.0, "any"),
    ("flatten", lambda t: P.flatten(t) ** 2, "any"),
    ("squeeze", lambda t: P.squeeze(P.unsqueeze(t, 0), 0) * t, "any"),
    ("unsqueeze", lambda t: P.unsqueeze(t, 1) * 3.0, "any"),
    ("concat", lambda t: P.concat([t, t], axis=0) ** 2, "any"),
    ("stack", lambda t: P.stack([t, t * 2]), "any"),
    ("split", lambda t: P.split(t, 2, axis=1)[0] ** 2, "any"),
    ("chunk", lambda t: P.chunk(t, 2, axis=0)[1] * 2.0, "any"),
    ("flip", lambda t: P.flip(t, axis=[1]) * t, "any"),
    ("roll", lambda t: P.roll(t, 1, axis=1) * 2.0, "any"),
    ("tile", lambda t: P.tile(t, [2, 1]) ** 2, "any"),
    ("expand", lambda t: P.expand(P.unsqueeze(t, 0), [2, 3, 4]) * 2.0, "any"),
    ("broadcast_to", lambda t: P.broadcast_to(t, [2, 3, 4]) ** 2, "any"),
    ("transpose", lambda t: P.transpose(t, [1, 0]) * t.T, "any"),
    ("gather", lambda t: P.gather(t, IDX, axis=0) * 2.0, "any"),
    ("index_select", lambda t: P.index_select(t, IDX, axis=0) ** 2, "any"),
    ("take_along_axis", lambda t: P.take_along_axis(t, P.to_tensor(np.zeros((3, 1), np.int64)), 1), "any"),
    ("tril", lambda t: P.tril(t) * 2.0, "any"),
    ("triu", lambda t: P.triu(t) ** 2, "any"),
    ("rot90", lambda t: P.rot90(t) * 2.0, "any"),
    ("moveaxis", lambda t: P.moveaxis(t, 0, 1) * 3.0, "any"),
    ("swapaxes", lambda t: P.swapaxes(t, 0, 1) ** 2, "any"),
    ("repeat_interleave", lambda t: P.repeat_interleave(t, 2, axis=0) * 2.0, "any"),
    ("masked_fill", lambda t: P.masked_fill(t, P.to_tensor(np.eye(3, 4) > 0), 0.0) * 2.0, "any"),
    ("where", lambda t: P.where(P.to_tensor(np.eye(3, 4) > 0), t * 2.0, t * 3.0), "any"),
    ("sort_vals", lambda t: P.sort(t, axis=1), "any"),
    ("unbind", lambda t: P.unbind(t, axis=0)[0] ** 2, "any"),
]

NN = [
    ("relu", lambda t: F.relu(t), "pos"),
    ("relu6", lambda t: F.relu6(t), "pos"),
    ("leaky_relu", lambda t: F.leaky_relu(t), "any"),
    ("elu", lambda t: F.elu(t), "any"),
    ("selu", lambda t: F.selu(t), "any"),
    ("celu", lambda t: F.celu(t), "any"),
    ("gelu", lambda t: F.gelu(t), "any"),
    ("silu", lambda t: F.silu(t), "any"),
    ("mish", lambda t: F.mish(t), "any"),
    ("softplus", lambda t: F.softplus(t), "any"),
    ("softsign", lambda t: F.softsign(t), "any"),
    ("tanhshrink", lambda t: F.tanhshrink(t), "any"),
    ("hardtanh", lambda t: F.hardtanh(t), "small"),
    ("hardsigmoid", lambda t: F.hardsigmoid(t), "small"),
    ("hardswish", lambda t: F.hardswish(t), "gt1"),
    ("log_sigmoid", lambda t: F.log_sigmoid(t), "any"),
    ("softmax", lambda t: F.softmax(t, axis=-1), "any"),
    ("log_softmax", lambda t: F.log_softmax(t, axis=-1), "any"),
    ("gumbel_softmax_tau", lambda t: F.softmax(t / 0.5, axis=-1), "any"),
    ("normalize", lambda t: F.normalize(t, axis=1), "pos"),
    ("dropout_eval", lambda t: F.dropout(t, p=0.5, training=False), "any"),
    ("linear", lambda t: F.linear(t, W34), "any"),
    ("mse_loss", lambda t: F.mse_loss(t, P.zeros_like(t)), "any"),
    ("l1_loss", lambda t: F.l1_loss(t, P.zeros_like(t) + 5.0), "pos"),
    ("smooth_l1", lambda t: F.smooth_l1_loss(t, P.zeros_like(t)), "any"),
    ("bce", lambda t: F.binary_cross_entropy(t, P.full_like(t, 0.7)), "unit"),
    ("bce_logits", lambda t: F.binary_cross_entropy_with_logits(t, P.full_like(t, 0.7)), "any"),
    ("kl_div", lambda t: F.kl_div(F.log_softmax(t, -1), F.softmax(P.ones_like(t), -1)), "any"),
    ("pad", lambda t: F.pad(t, [1, 1], mode="constant", value=0.0) * 2.0, "any"),
    ("layer_norm_in", lambda t: F.layer_norm(t, [4], None, None, 1e-5), "any"),
]

LINALG = [
    ("cholesky", lambda t: P.linalg.cholesky(P.matmul(t, t, transpose_y=True) + 3.0 * P.eye(3)), "any"),
    ("inv", lambda t: P.linalg.inv(t + 4.0 * P.eye(3)), "small"),
    ("det", lambda t: P.linalg.det(t + 4.0 * P.eye(3)), "small"),
    ("slogdet_val", lambda t: P.linalg.slogdet(t + 4.0 * P.eye(3))[1], "small"),
    ("solve", lambda t: P.linalg.solve(t + 4.0 * P.eye(3), P.ones([3, 1])), "small"),
    ("triangular_solve", lambda t: P.linalg.triangular_solve(P.tril(t) + 4.0 * P.eye(3), P.ones([3, 1]), upper=False), "small"),
    ("norm_fro", lambda t: P.linalg.norm(t), "any"),
    ("norm_1", lambda t: P.linalg.norm(t, p=1, axis=1), "pos"),
    ("dist", lambda t: P.dist(t, P.zeros_like(t), p=2), "pos"),
    ("cross", lambda t: P.cross(t, P.ones_like(t), axis=1), "any", (3, 3)),
    ("cov", lambda t: P.linalg.cov(t), "any"),
    ("matrix_power", lambda t: P.linalg.matrix_power(t, 2), "small", (3, 3)),
    ("pinv", lambda t: P.linalg.pinv(t + 4.0 * P.eye(3)), "small", (3, 3)),
    ("eigh_vals", lambda t: P.linalg.eigvalsh(P.matmul(t, t, transpose_y=True) + P.eye(3)), "small", (3, 3)),
    ("svdvals", lambda t: P.linalg.svd(t)[1], "any", (3, 3)),
]

ALL_CASES = []
for table in (UNARY, BINARY, REDUCE, MATMUL, MANIP, NN, LINALG):
    for entry in table:
        name, op, dom = entry[0], entry[1], entry[2]
        shape = entry[3] if len(entry) > 3 else ((3, 3) if table is LINALG else (3, 4))
        ALL_CASES.append((name, op, dom, shape))

assert len(ALL_CASES) >= 100, f"only {len(ALL_CASES)} grad-checked ops"


@pytest.mark.parametrize("name,op,dom,shape", ALL_CASES, ids=[c[0] for c in ALL_CASES])
def test_grad_matches_numeric(name, op, dom, shape):
    check(op, _dom(dom, shape))
