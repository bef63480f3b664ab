"""The nn.functional op tail (parity: the remaining exports of
/root/reference/python/paddle/nn/functional/__init__.py) — grid sampling,
pooling variants with indices, the loss tail, margin softmax, beam-search
helpers, transducer loss, and in-place activation aliases.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.dispatch import apply
from ...tensor._helpers import to_tensor_like as _t
from ...tensor.tensor import Tensor
from . import activation as _act

__all__ = [
    "affine_grid", "grid_sample", "sequence_mask", "temporal_shift",
    "dice_loss", "npair_loss", "pairwise_distance", "gaussian_nll_loss",
    "multi_margin_loss", "triplet_margin_with_distance_loss", "hsigmoid_loss",
    "class_center_sample", "margin_cross_entropy", "gather_tree", "rnnt_loss",
    "max_unpool1d", "max_unpool2d", "max_unpool3d", "lp_pool1d", "lp_pool2d",
    "fractional_max_pool2d", "fractional_max_pool3d", "feature_alpha_dropout",
    "adaptive_log_softmax_with_loss", "flash_attn_qkvpacked",
    "flash_attn_varlen_qkvpacked", "flash_attention_with_sparse_mask",
    "sparse_attention", "thresholded_relu_", "tanh_", "leaky_relu_", "hardtanh_",
    "max_pool2d_with_index",
]


# ---------------------------------------------------------------- sampling
def affine_grid(theta, out_shape, align_corners=True, name=None):
    """theta [N,2,3] -> sampling grid [N,H,W,2] (paddle/torch convention)."""
    theta = _t(theta)
    n, h, w = int(out_shape[0]), int(out_shape[2]), int(out_shape[3])

    def f(th):
        if align_corners:
            ys = jnp.linspace(-1, 1, h)
            xs = jnp.linspace(-1, 1, w)
        else:
            ys = (jnp.arange(h) + 0.5) * 2 / h - 1
            xs = (jnp.arange(w) + 0.5) * 2 / w - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [H,W,3]
        return jnp.einsum("hwk,nck->nhwc", base, th)

    return apply(f, theta, op_name="affine_grid")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    """x [N,C,H,W], grid [N,Ho,Wo,2] in [-1,1] -> [N,C,Ho,Wo]."""
    x, grid = _t(x), _t(grid)

    def f(xv, gv):
        N, C, H, W = xv.shape

        def unnorm(g, size):
            if align_corners:
                return (g + 1) * (size - 1) / 2
            return ((g + 1) * size - 1) / 2

        gx = unnorm(gv[..., 0], W)
        gy = unnorm(gv[..., 1], H)

        def sample_n(fm, yy, xx):
            if mode == "nearest":
                yi = jnp.clip(jnp.round(yy), 0, H - 1).astype(jnp.int32)
                xi = jnp.clip(jnp.round(xx), 0, W - 1).astype(jnp.int32)
                out = fm[:, yi, xi]
                if padding_mode == "zeros":
                    inb = (yy >= -0.5) & (yy <= H - 0.5) & (xx >= -0.5) & (xx <= W - 0.5)
                    out = jnp.where(inb[None], out, 0.0)
                return out
            y0 = jnp.floor(yy)
            x0 = jnp.floor(xx)
            wy = yy - y0
            wx = xx - x0
            vals = 0.0
            for dy, sy in ((0, 1 - wy), (1, wy)):
                for dx, sx in ((0, 1 - wx), (1, wx)):
                    yi = y0 + dy
                    xi = x0 + dx
                    yc = jnp.clip(yi, 0, H - 1).astype(jnp.int32)
                    xc = jnp.clip(xi, 0, W - 1).astype(jnp.int32)
                    v = fm[:, yc, xc]
                    if padding_mode == "zeros":
                        inb = (yi >= 0) & (yi <= H - 1) & (xi >= 0) & (xi <= W - 1)
                        v = jnp.where(inb[None], v, 0.0)
                    vals = vals + v * (sy * sx)[None]
            return vals

        return jax.vmap(sample_n)(xv, gy, gx)

    return apply(f, x, grid, op_name="grid_sample")


# ----------------------------------------------------------------- sequence
def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    x = _t(x)
    m = int(maxlen) if maxlen is not None else int(np.asarray(jnp.max(x._value)))
    from ...framework.dtype import to_jax_dtype

    dt = to_jax_dtype(dtype)
    return apply(lambda v: (jnp.arange(m) < v[..., None]).astype(dt), x,
                 op_name="sequence_mask")


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None, data_format="NCHW"):
    x = _t(x)

    def f(v):
        nt, c, h, w = v.shape
        n = nt // seg_num
        r = v.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([r[:, 1:, :fold], jnp.zeros_like(r[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(r[:, :1, fold:2 * fold]),
                                 r[:, :-1, fold:2 * fold]], axis=1)
        rest = r[:, :, 2 * fold:]
        return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)

    return apply(f, x, op_name="temporal_shift")


def gather_tree(ids, parents):
    """Beam-search backtrace: [T, B, beam] ids/parents -> full sequences."""
    ids, parents = _t(ids), _t(parents)

    def f(idv, pv):
        T = idv.shape[0]

        def step(beams, t):
            # beams: current beam index per [B, beam] at time t+1
            cur_ids = jnp.take_along_axis(idv[t], beams, axis=-1)
            prev = jnp.take_along_axis(pv[t], beams, axis=-1)
            return prev, cur_ids

        init = jnp.broadcast_to(jnp.arange(idv.shape[2]), idv.shape[1:])
        _, seq = lax.scan(step, init, jnp.arange(T - 1, -1, -1))
        return seq[::-1]

    return apply(f, ids, parents, op_name="gather_tree")


# -------------------------------------------------------------------- losses
def _reduce(v, reduction):
    if reduction == "mean":
        return jnp.mean(v)
    if reduction == "sum":
        return jnp.sum(v)
    return v


def dice_loss(input, label, epsilon=1e-5, name=None):  # noqa: A002
    input, label = _t(input), _t(label)

    def f(p, l):  # noqa: E741
        if l.ndim == p.ndim and l.shape[-1] == 1:
            l = jax.nn.one_hot(l[..., 0].astype(jnp.int32), p.shape[-1])  # noqa: E741
        l = l.astype(p.dtype)  # noqa: E741
        red = tuple(range(1, p.ndim))
        inter = jnp.sum(p * l, axis=red)
        return jnp.mean(1 - (2 * inter) / (jnp.sum(p, red) + jnp.sum(l, red) + epsilon))

    return apply(f, input, label, op_name="dice_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    anchor, positive, labels = _t(anchor), _t(positive), _t(labels)

    def f(a, p, y):
        sim = a @ p.T  # [B, B]
        same = (y[:, None] == y[None, :]).astype(a.dtype)
        same = same / jnp.sum(same, axis=1, keepdims=True)
        xent = jnp.mean(jnp.sum(-same * jax.nn.log_softmax(sim, axis=1), axis=1))
        reg = l2_reg * (jnp.mean(jnp.sum(a * a, 1)) + jnp.mean(jnp.sum(p * p, 1))) / 2
        return xent + reg

    return apply(f, anchor, positive, labels, op_name="npair_loss")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    x, y = _t(x), _t(y)
    return apply(lambda a, b: jnp.sum(jnp.abs(a - b + epsilon) ** p, -1,
                                      keepdims=keepdim) ** (1.0 / p),
                 x, y, op_name="pairwise_distance")


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,  # noqa: A002
                      reduction="mean", name=None):
    input, label, variance = _t(input), _t(label), _t(variance)

    def f(mu, y, var):
        var = jnp.maximum(var, epsilon)
        loss = 0.5 * (jnp.log(var) + (y - mu) ** 2 / var)
        if full:
            loss = loss + 0.5 * math.log(2 * math.pi)
        return _reduce(loss, reduction)

    return apply(f, input, label, variance, op_name="gaussian_nll_loss")


def multi_margin_loss(input, label, p: int = 1, margin: float = 1.0,  # noqa: A002
                      weight=None, reduction="mean", name=None):
    input, label = _t(input), _t(label)
    args = [input, label] + ([_t(weight)] if weight is not None else [])

    def f(x, y, *w):
        n, c = x.shape
        y = y.astype(jnp.int32)
        xy = jnp.take_along_axis(x, y[:, None], axis=1)
        m = jnp.maximum(0.0, margin - xy + x) ** p
        if w:
            m = m * w[0][y][:, None]
        m = m.at[jnp.arange(n), y].set(0.0)
        return _reduce(jnp.sum(m, axis=1) / c, reduction)

    return apply(f, *args, op_name="multi_margin_loss")


def triplet_margin_with_distance_loss(input, positive, negative,  # noqa: A002
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean", name=None):
    input, positive, negative = _t(input), _t(positive), _t(negative)
    if distance_function is None:
        def dist(a, b):
            return jnp.sqrt(jnp.maximum(jnp.sum((a - b) ** 2, -1), 1e-12))
    else:
        def dist(a, b):
            out = distance_function(Tensor(a), Tensor(b))
            return out._value if isinstance(out, Tensor) else out

    def f(a, p, n):
        dp = dist(a, p)
        dn = dist(a, n)
        if swap:
            dn = jnp.minimum(dn, dist(p, n))
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)

    return apply(f, input, positive, negative, op_name="triplet_margin_with_distance")


def hsigmoid_loss(input, label, num_classes, weight, bias=None,  # noqa: A002
                  path_table=None, path_code=None, is_sparse=False, name=None):
    """Hierarchical sigmoid over a complete binary tree (default paths) or
    user-supplied path_table/path_code (reference hsigmoid_loss op)."""
    input, label, weight = _t(input), _t(label), _t(weight)
    if path_table is None:
        # default complete binary tree over num_classes leaves: internal
        # node ids 0..num_classes-2; leaf k's path from the root
        depth = max(1, int(np.ceil(np.log2(max(num_classes, 2)))))
        tbl = np.zeros((num_classes, depth), np.int32)
        code = np.zeros((num_classes, depth), np.float32)
        lens = np.zeros(num_classes, np.int32)
        for k in range(num_classes):
            node = k + num_classes - 1  # leaf position in a heap layout
            path = []
            bits = []
            while node > 0:
                parent = (node - 1) // 2
                bits.append(float(node == 2 * parent + 2))  # right child -> 1
                path.append(parent)
                node = parent
            path.reverse()
            bits.reverse()
            lens[k] = len(path)
            tbl[k, :len(path)] = path
            code[k, :len(bits)] = bits
        path_table = Tensor(jnp.asarray(tbl))
        path_code = Tensor(jnp.asarray(code))
        lengths = jnp.asarray(lens)
    else:
        path_table, path_code = _t(path_table), _t(path_code)
        lengths = jnp.sum((path_table._value >= 0).astype(jnp.int32), axis=-1)

    args = [input, label, weight, path_table, path_code] + \
        ([_t(bias)] if bias is not None else [])

    def f(x, y, w, tbl, code, *b):
        y = y.astype(jnp.int32).reshape(-1)
        nodes = tbl[y]  # [B, D]
        codes = code[y].astype(x.dtype)
        ln = lengths[y]
        logits = jnp.einsum("bf,bdf->bd", x, w[nodes])
        if b:
            logits = logits + b[0][nodes]
        # bce with the path code as the target at each internal node
        ll = jax.nn.log_sigmoid(logits) * (1 - codes) + jax.nn.log_sigmoid(-logits) * codes
        mask = jnp.arange(nodes.shape[1])[None, :] < ln[:, None]
        return jnp.mean(-jnp.sum(jnp.where(mask, ll, 0.0), axis=1))

    return apply(f, *args, op_name="hsigmoid_loss")


def class_center_sample(label, num_classes, num_samples, group=None):
    """Sample class centers: all positives + random negatives (PartialFC)."""
    label = _t(label)
    lv = np.asarray(label._value).reshape(-1)
    pos = np.unique(lv)
    if len(pos) >= num_samples:
        sampled = pos
    else:
        neg_pool = np.setdiff1d(np.arange(num_classes), pos)
        # fresh negatives per call, seeded from the framework RNG stream so
        # paddle.seed() keeps runs reproducible (the reference PartialFC op
        # resamples each step; a frozen pool degrades margin-softmax training)
        from ...framework import random as _fr

        gen = _fr.default_generator()
        seed_ = int(jax.random.randint(gen.next_key(), (), 0, 2**31 - 1))
        extra = np.random.RandomState(seed_).choice(
            neg_pool, size=min(num_samples - len(pos), len(neg_pool)), replace=False)
        sampled = np.concatenate([pos, np.sort(extra)])
    remap = {c: i for i, c in enumerate(sampled)}
    remapped = np.asarray([remap[v] for v in lv], np.int64)
    return Tensor(jnp.asarray(remapped)), Tensor(jnp.asarray(sampled.astype(np.int64)))


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5, margin3=0.0,
                         scale=64.0, group=None, return_softmax=False,
                         reduction="mean"):
    """ArcFace-family margin softmax: cos(m1*theta + m2) - m3 on the target
    logit (reference margin_cross_entropy op)."""
    logits, label = _t(logits), _t(label)

    def f(lg, y):
        y = y.astype(jnp.int32).reshape(-1)
        cos = jnp.clip(lg, -1.0, 1.0)
        target = jnp.take_along_axis(cos, y[:, None], axis=1)[:, 0]
        theta = jnp.arccos(jnp.clip(target, -1 + 1e-7, 1 - 1e-7))
        m_target = jnp.cos(margin1 * theta + margin2) - margin3
        adjusted = cos.at[jnp.arange(cos.shape[0]), y].set(m_target) * scale
        lse = jax.scipy.special.logsumexp(adjusted, axis=1)
        loss = lse - jnp.take_along_axis(adjusted, y[:, None], axis=1)[:, 0]
        sm = jax.nn.softmax(adjusted, axis=1)
        return _reduce(loss, reduction), sm

    loss, sm = apply(f, logits, label, op_name="margin_cross_entropy", n_outs=2)
    return (loss, sm) if return_softmax else loss


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,  # noqa: A002
              fastemit_lambda=0.001, reduction="mean", name=None):
    """RNN-Transducer loss — log-alpha DP over the (T, U) lattice with a
    lax.scan over time (reference binds warprnnt; this is the pure-XLA DP)."""
    input, label = _t(input), _t(label)
    input_lengths, label_lengths = _t(input_lengths), _t(label_lengths)

    def f(lp, lab, in_len, lab_len):
        # lp: [B, T, U+1, V] logits
        lp = jax.nn.log_softmax(lp, axis=-1)
        B, T, U1, V = lp.shape
        lab = lab.astype(jnp.int32)
        blank_lp = lp[..., blank]  # [B, T, U+1]
        # emit log-probs: lp[b, t, u, lab[b, u]] for u < U
        emit_lp = jnp.take_along_axis(
            lp[:, :, :-1, :], lab[:, None, :, None], axis=-1)[..., 0]  # [B,T,U]
        if fastemit_lambda:
            # FastEmit regularization (warprnnt binding semantics): the loss
            # value is unchanged but the gradient flowing through emit
            # transitions is scaled by (1 + lambda), encouraging earlier
            # emission. Value-preserving autodiff form of that reweighting:
            emit_lp = (1.0 + fastemit_lambda) * emit_lp \
                - fastemit_lambda * lax.stop_gradient(emit_lp)
        neg_inf = -1e30

        def step(alpha, t):
            # alpha: [B, U+1] at time t; advance to t+1
            # emit transitions within time t: alpha[u] + emit(t, u) -> alpha[u+1]
            def inner(carry, u):
                a = carry
                from_left = a[:, u] + emit_lp[:, t, u]
                new = jnp.logaddexp(a[:, u + 1], from_left)
                a = a.at[:, u + 1].set(new)
                return a, None

            alpha_e, _ = lax.scan(inner, alpha, jnp.arange(U1 - 1))
            # blank transition to t+1 (time advance, all u)
            nxt = alpha_e + blank_lp[:, t, :]
            active = (t < in_len)[:, None]
            return jnp.where(active, nxt, alpha), None

        alpha0 = jnp.full((B, U1), neg_inf).at[:, 0].set(0.0)
        # alpha after processing all time steps = total log-prob at [T-1, U]
        # We need alpha THROUGH emits at the final time before last blank;
        # run scan over t, capturing final-time emission handled inside.
        alphaT, _ = lax.scan(step, alpha0, jnp.arange(T))
        # total log prob: alpha at u = lab_len after the final blank at t=in_len-1
        ll = jnp.take_along_axis(alphaT, lab_len.astype(jnp.int32)[:, None], axis=1)[:, 0]
        return _reduce(-ll, reduction)

    return apply(f, input, label, input_lengths, label_lengths, op_name="rnnt_loss")


# ------------------------------------------------------------- pool variants
def max_pool2d_with_index(x, kernel_size, stride=None, padding=0):
    """-> (pooled, flat indices into each input map [H*W]) — feeds unpool."""
    x = _t(x)
    ks = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
    st = ks if stride is None else ((stride, stride) if isinstance(stride, int) else tuple(stride))
    pd = (padding, padding) if isinstance(padding, int) else tuple(padding)

    def f(v):
        N, C, H, W = v.shape
        vp = jnp.pad(v, ((0, 0), (0, 0), (pd[0], pd[0]), (pd[1], pd[1])),
                     constant_values=-jnp.inf)
        Hp, Wp = vp.shape[-2:]
        oh = (Hp - ks[0]) // st[0] + 1
        ow = (Wp - ks[1]) // st[1] + 1
        iy = (jnp.arange(oh) * st[0])[:, None, None, None] + jnp.arange(ks[0])[None, None, :, None]
        ix = (jnp.arange(ow) * st[1])[None, :, None, None] + jnp.arange(ks[1])[None, None, None, :]
        iy = jnp.broadcast_to(iy, (oh, ow, ks[0], ks[1]))
        ix = jnp.broadcast_to(ix, (oh, ow, ks[0], ks[1]))
        win = vp[:, :, iy, ix].reshape(N, C, oh, ow, -1)
        arg = jnp.argmax(win, axis=-1)
        pooled = jnp.max(win, axis=-1)
        wy = iy.reshape(oh, ow, -1)
        wx = ix.reshape(oh, ow, -1)
        sel_y = jnp.take_along_axis(
            jnp.broadcast_to(wy[None, None], (N, C, oh, ow, wy.shape[-1])), arg[..., None], -1)[..., 0]
        sel_x = jnp.take_along_axis(
            jnp.broadcast_to(wx[None, None], (N, C, oh, ow, wx.shape[-1])), arg[..., None], -1)[..., 0]
        flat = (sel_y - pd[0]) * W + (sel_x - pd[1])
        return pooled, flat.astype(jnp.int32)

    out = apply(f, x, op_name="max_pool2d_with_index", n_outs=2)
    return out[0], out[1]


def _max_unpool(x, indices, nd, kernel_size, stride, padding, output_size):
    x, indices = _t(x), _t(indices)
    ks = (kernel_size,) * nd if isinstance(kernel_size, int) else tuple(kernel_size)
    st = ks if stride is None else ((stride,) * nd if isinstance(stride, int) else tuple(stride))
    pd = (padding,) * nd if isinstance(padding, int) else tuple(padding)
    if output_size is None:
        spatial = [(s - 1) * st[i] + ks[i] - 2 * pd[i]
                   for i, s in enumerate(x._value.shape[2:])]
    else:
        spatial = list(output_size)[-nd:]
    total = int(np.prod(spatial))

    def f(v, idx):
        N, C = v.shape[:2]
        flatv = v.reshape(N, C, -1)
        flati = idx.reshape(N, C, -1).astype(jnp.int32)
        out = jnp.zeros((N, C, total), v.dtype)
        out = jax.vmap(jax.vmap(lambda o, i, s: o.at[i].set(s)))(out, flati, flatv)
        return out.reshape(N, C, *spatial)

    return apply(f, x, indices, op_name=f"max_unpool{nd}d")


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None, name=None):
    return _max_unpool(x, indices, 1, kernel_size, stride, padding, output_size)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    return _max_unpool(x, indices, 2, kernel_size, stride, padding, output_size)


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None, name=None):
    return _max_unpool(x, indices, 3, kernel_size, stride, padding, output_size)


def lp_pool1d(x, norm_type, kernel_size, stride=None, padding=0, ceil_mode=False,
              data_format="NCL", name=None):
    from .pooling import avg_pool1d

    x = _t(x)
    p = float(norm_type)
    powed = apply(lambda v: jnp.abs(v) ** p, x, op_name="lp_pow")
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    avg = avg_pool1d(powed, kernel_size, stride, padding, ceil_mode=ceil_mode)
    return apply(lambda v: (v * k) ** (1.0 / p), avg, op_name="lp_root")


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0, ceil_mode=False,
              data_format="NCHW", name=None):
    from .pooling import avg_pool2d

    x = _t(x)
    p = float(norm_type)
    powed = apply(lambda v: jnp.abs(v) ** p, x, op_name="lp_pow")
    ks = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
    avg = avg_pool2d(powed, kernel_size, stride, padding, ceil_mode=ceil_mode)
    return apply(lambda v: (v * ks[0] * ks[1]) ** (1.0 / p), avg, op_name="lp_root")


def _fractional_regions(in_size, out_size, u):
    """Pseudo-random pooling boundaries (Graham's fractional max pooling)."""
    alpha = in_size / out_size
    idx = np.floor(alpha * (np.arange(out_size) + u)).astype(int)
    idx = np.clip(idx, 0, in_size - 1)
    idx[0] = 0
    ends = np.append(idx[1:], in_size)
    ends = np.maximum(ends, idx + 1)
    return idx, ends


def fractional_max_pool2d(x, output_size, kernel_size=None, random_u=None,
                          return_mask=False, name=None):
    x = _t(x)
    N, C, H, W = x._value.shape
    oh, ow = (output_size, output_size) if isinstance(output_size, int) else tuple(output_size)
    u = float(random_u) if random_u is not None else 0.5
    ys, ye = _fractional_regions(H, oh, u)
    xs, xe = _fractional_regions(W, ow, u)
    maxk_h = int((ye - ys).max())
    maxk_w = int((xe - xs).max())
    iy = np.minimum(ys[:, None] + np.arange(maxk_h)[None, :], H - 1)
    ix = np.minimum(xs[:, None] + np.arange(maxk_w)[None, :], W - 1)
    vy = (ys[:, None] + np.arange(maxk_h)[None, :]) < ye[:, None]
    vx = (xs[:, None] + np.arange(maxk_w)[None, :]) < xe[:, None]
    iyj, ixj = jnp.asarray(iy), jnp.asarray(ix)
    valid = jnp.asarray(vy[:, None, :, None] & vx[None, :, None, :])

    def f(v):
        win = v[:, :, iyj[:, None, :, None], ixj[None, :, None, :]]
        win = jnp.where(valid[None, None], win, -jnp.inf)
        return jnp.max(win, axis=(-2, -1))

    out = apply(f, x, op_name="fractional_max_pool2d")
    if return_mask:
        return out, None
    return out


def fractional_max_pool3d(x, output_size, kernel_size=None, random_u=None,
                          return_mask=False, name=None):
    x = _t(x)
    N, C, D, H, W = x._value.shape
    od, oh, ow = (output_size,) * 3 if isinstance(output_size, int) else tuple(output_size)
    u = float(random_u) if random_u is not None else 0.5
    ds, de = _fractional_regions(D, od, u)
    ys, ye = _fractional_regions(H, oh, u)
    xs, xe = _fractional_regions(W, ow, u)

    def f(v):
        outs = []
        for di in range(od):
            sl = v[:, :, ds[di]:de[di]]
            dmax = jnp.max(sl, axis=2)
            rows = []
            for yi in range(oh):
                seg = dmax[:, :, ys[yi]:ye[yi]]
                ymax = jnp.max(seg, axis=2)
                cols = [jnp.max(ymax[:, :, xs[xi]:xe[xi]], axis=2) for xi in range(ow)]
                rows.append(jnp.stack(cols, axis=-1))
            outs.append(jnp.stack(rows, axis=-2))
        return jnp.stack(outs, axis=-3)

    return apply(f, x, op_name="fractional_max_pool3d")


# ------------------------------------------------------------------ dropout
def feature_alpha_dropout(x, p=0.5, training=True, name=None):
    """Alpha dropout over whole channels (SELU-preserving statistics)."""
    x = _t(x)
    if not training or p == 0.0:
        return apply(lambda v: v, x, op_name="feature_alpha_dropout")
    from ...framework.random import default_generator

    key = default_generator().next_key()
    alpha = -1.7580993408473766
    a = ((1 - p) * (1 + p * alpha ** 2)) ** -0.5
    b = -a * alpha * p

    def f(v):
        shape = (v.shape[0], v.shape[1]) + (1,) * (v.ndim - 2)
        keep = jax.random.bernoulli(key, 1 - p, shape)
        return (jnp.where(keep, v, alpha) * a + b).astype(v.dtype)

    return apply(f, x, op_name="feature_alpha_dropout")


# ------------------------------------------------- adaptive softmax / attn
def adaptive_log_softmax_with_loss(input, label, head_weight, tail_weights,  # noqa: A002
                                   cutoffs, head_bias=None, name=None):
    """Efficient softmax over frequency-clustered vocab (reference
    adaptive_log_softmax_with_loss). Returns (per-sample logprob, loss)."""
    input, label, head_weight = _t(input), _t(label), _t(head_weight)
    tails = [[_t(w) for w in pair] for pair in tail_weights]
    n_clusters = len(cutoffs)
    head_size = cutoffs[0] + n_clusters
    args = [input, label, head_weight] + [w for pair in tails for w in pair] + \
        ([_t(head_bias)] if head_bias is not None else [])
    has_bias = head_bias is not None

    def f(x, y, hw, *rest):
        flat_tails = rest[: 2 * n_clusters]
        hb = rest[-1] if has_bias else None
        y = y.astype(jnp.int32)
        head_logits = x @ hw
        if hb is not None:
            head_logits = head_logits + hb
        head_lsm = jax.nn.log_softmax(head_logits, axis=-1)
        out = jnp.zeros(y.shape, x.dtype)
        in_head = y < cutoffs[0]
        out = jnp.where(in_head,
                        jnp.take_along_axis(head_lsm, jnp.clip(y, 0, cutoffs[0] - 1)[:, None], 1)[:, 0],
                        out)
        low = cutoffs[0]
        for ci in range(n_clusters):
            proj, cls_w = flat_tails[2 * ci], flat_tails[2 * ci + 1]
            tail_lsm = jax.nn.log_softmax((x @ proj) @ cls_w, axis=-1)
            upper = cutoffs[ci + 1] if ci + 1 < len(cutoffs) else low + tail_lsm.shape[-1]
            in_c = (y >= low) & (y < upper)
            rel = jnp.clip(y - low, 0, tail_lsm.shape[-1] - 1)
            cluster_lp = head_lsm[:, cutoffs[0] + ci] + \
                jnp.take_along_axis(tail_lsm, rel[:, None], 1)[:, 0]
            out = jnp.where(in_c, cluster_lp, out)
            low = upper
        return out, -jnp.mean(out)

    out = apply(f, *args, op_name="adaptive_log_softmax_with_loss", n_outs=2)
    return out[0], out[1]


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False, return_softmax=False,
                         *, training=True, name=None):
    """qkv [B, S, 3, H, D] packed — routes to the Pallas flash kernel."""
    from .flash_attention import flash_attention

    qkv = _t(qkv)
    from ...tensor.manipulation import squeeze, split as _split

    parts = _split(qkv, 3, axis=2)
    q, k, v = (squeeze(p, 2) for p in parts)
    return flash_attention(q, k, v, dropout=dropout, causal=causal,
                           return_softmax=return_softmax, training=training)


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                                max_seqlen_k, scale=None, dropout=0.0,
                                causal=False, return_softmax=False,
                                fixed_seed_offset=None, rng_name="",
                                varlen_padded=True, training=True, name=None):
    """parity: flash_attn_varlen_qkvpacked (flash_attention.py:863) — packed
    qkv [total, num_heads/num_heads_k + 2, num_heads_k, head_dim]; the
    first (H/KV) groups are query heads, the last two are K and V.
    ``varlen_padded=True``: tokens live at ``b*max_seqlen + i`` with padding
    rows uncomputed (the reference contract). Returns (out [total, H, D],
    None)."""
    import jax.numpy as jnp

    from .flash_attention import varlen_attention_core

    qkv = _t(qkv)
    cu_q = _t(cu_seqlens_q)
    cu_k = _t(cu_seqlens_k)
    drop = float(dropout) if training else 0.0
    drop_key = None
    if drop > 0.0:
        from ...framework.random import default_generator

        drop_key = default_generator().next_key()

    def f(pk, cq, ck):
        total, G, KV, D = pk.shape
        q = pk[:, :G - 2].reshape(total, (G - 2) * KV, D)
        k = pk[:, G - 2]
        v = pk[:, G - 1]
        return varlen_attention_core(
            q, k, v, cq.reshape(-1).astype(jnp.int32),
            ck.reshape(-1).astype(jnp.int32), int(max_seqlen_q),
            int(max_seqlen_k), scale, causal, drop, drop_key,
            padded_layout=bool(varlen_padded))

    out = apply(f, qkv, cu_q, cu_k, op_name="flash_attn_varlen_qkvpacked")
    return out, None


def flash_attention_with_sparse_mask(query, key, value,
                                     attn_mask_start_row_indices,
                                     attn_mask_start_row=0, dropout_p=0.0,
                                     is_causal=True, return_softmax=False,
                                     return_softmax_lse=False,
                                     return_seed_offset=False, training=True,
                                     name=None):
    """parity: flash_attention_with_sparse_mask (flash_attention.py:1113) —
    column-wise mask-start rows: score[i, j] is masked when
    ``i >= attn_mask_start_row_indices[b, h, j]`` (on top of the causal
    triangle). This is the reference's packed-sequence/startend-row sparse
    mask; lowered to one masked fp32-softmax attention (XLA fuses the mask —
    measured faster than custom kernels on v5e at r4)."""
    import jax
    import jax.numpy as jnp

    q, k, v = _t(query), _t(key), _t(value)
    idx = _t(attn_mask_start_row_indices)
    drop = float(dropout_p) if training else 0.0
    drop_key = None
    if drop > 0.0:
        from ...framework.random import default_generator

        drop_key = default_generator().next_key()

    def f(qv, kv, vv, ix):
        B, S, H, D = qv.shape
        KV = kv.shape[2]
        if KV != H:
            kv = jnp.repeat(kv, H // KV, axis=2)
            vv = jnp.repeat(vv, H // KV, axis=2)
        qh = jnp.moveaxis(qv, 2, 1).astype(jnp.float32)  # [B,H,S,D]
        kh = jnp.moveaxis(kv, 2, 1).astype(jnp.float32)
        vh = jnp.moveaxis(vv, 2, 1).astype(jnp.float32)
        logits = jnp.einsum("bhid,bhjd->bhij", qh, kh) / (D ** 0.5)
        i = jnp.arange(S, dtype=jnp.int32)
        allowed = i[:, None] < ix[:, :, None, :]  # [B,H,S(i),S(j)]
        if is_causal:
            allowed = allowed & (i[None, None, :, None] >= i[None, None, None, :])
        logits = jnp.where(allowed, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        if drop > 0.0 and drop_key is not None:
            keep = jax.random.bernoulli(drop_key, 1.0 - drop, p.shape)
            p = jnp.where(keep, p / (1.0 - drop), 0.0)
        o = jnp.einsum("bhij,bhjd->bhid", p, vh)
        return jnp.moveaxis(o, 1, 2).astype(qv.dtype)

    return apply(f, q, k, v, idx, op_name="flash_attention_sparse_mask")


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """CSR block-sparse attention (parity:
    /root/reference/python/paddle/nn/functional/sparse_attention.py:22):
    q/k/v [B, H, S, D]; offset [B, H, S+1] + columns [B, H, nnz] select
    which key columns each query row attends. TPU-native: the fixed nnz
    layout is a static gather — per-edge logits + segment-softmax
    (segment_max/segment_sum over the row ids), all MXU/VPU friendly and
    jit-safe (the reference needs a CUDA-11.3 cusparse kernel)."""
    import jax
    import jax.numpy as jnp

    q, k, v = _t(query), _t(key), _t(value)
    off = _t(sparse_csr_offset)
    cols = _t(sparse_csr_columns)
    kpm = _t(key_padding_mask) if key_padding_mask is not None else None
    am = _t(attn_mask) if attn_mask is not None else None
    args = [q, k, v, off, cols] + [t for t in (kpm, am) if t is not None]

    def f(qv, kv, vv, ov, cv, *rest):
        rest = list(rest)
        kp = rest.pop(0) if kpm is not None else None
        ms = rest.pop(0) if am is not None else None
        B, H, S, D = qv.shape
        nnz = cv.shape[-1]
        if kp is not None and kp.ndim == 2:  # [B, S] -> broadcast heads
            kp = jnp.broadcast_to(kp[:, None, :], (B, H, S))
        if ms is not None and ms.ndim == 2:  # [S, S] -> broadcast (B, H)
            ms = jnp.broadcast_to(ms[None, None], (B, H, S, S))

        def one(qh, kh, vh, oh, ch, kph, msh=None):
            # row id of each CSR edge; edges past offset[-1] are dead padding
            e = jnp.arange(nnz, dtype=jnp.int32)
            row = jnp.clip(
                jnp.searchsorted(oh.astype(jnp.int32), e, side="right") - 1,
                0, S - 1).astype(jnp.int32)
            live = e < oh[-1]
            col = jnp.clip(ch.astype(jnp.int32), 0, S - 1)
            lg = jnp.sum(qh[row].astype(jnp.float32)
                         * kh[col].astype(jnp.float32), -1) / (D ** 0.5)
            # reference mask semantics (fused sparse-attention kernel):
            # value == 0 means FULLY MASKED, nonzero means attendable —
            # these are 0/1 masks, not additive biases
            lg = jnp.where(kph[col] == 0, -1e30, lg)
            if msh is not None:  # [S, S] 0/1 mask, gathered per edge
                lg = jnp.where(msh[row, col] == 0, -1e30, lg)
            lg = jnp.where(live, lg, -1e30)
            mx = jax.ops.segment_max(lg, row, num_segments=S)
            ex = jnp.where(live, jnp.exp(lg - mx[row]), 0.0)
            den = jax.ops.segment_sum(ex, row, num_segments=S)
            w = ex / jnp.maximum(den[row], 1e-30)
            out = jax.ops.segment_sum(w[:, None] * vh[col].astype(jnp.float32),
                                      row, num_segments=S)
            return out.astype(qh.dtype)

        def flat(t, nbatch=2):
            return t.reshape((B * H,) + t.shape[nbatch:])

        kp_full = flat(kp) if kp is not None else jnp.ones(
            (B * H, S), jnp.float32)  # ones = nothing masked
        base = (flat(qv), flat(kv), flat(vv), flat(ov), flat(cv), kp_full)
        if ms is not None:
            outs = jax.vmap(one)(*base, flat(ms))
        else:
            outs = jax.vmap(lambda *a: one(*a))(*base)
        return outs.reshape(B, H, S, D)

    return apply(f, *args, op_name="sparse_attention")


# ------------------------------------------------------- in-place activations
def thresholded_relu_(x, threshold=1.0, name=None):
    from .activation import thresholded_relu

    return x._inplace_adopt(thresholded_relu(x, threshold))


def tanh_(x, name=None):
    from ...tensor.math import tanh

    return x._inplace_adopt(tanh(x))


def leaky_relu_(x, negative_slope=0.01, name=None):
    from .activation import leaky_relu

    return x._inplace_adopt(leaky_relu(x, negative_slope))


def hardtanh_(x, min=-1.0, max=1.0, name=None):  # noqa: A002
    from .activation import hardtanh

    return x._inplace_adopt(hardtanh(x, min, max))
