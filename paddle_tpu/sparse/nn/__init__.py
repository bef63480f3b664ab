"""paddle.sparse.nn parity (/root/reference/python/paddle/sparse/nn):
activations on sparse values, BatchNorm over the dense feature axis, and
conv layers.

TPU stance: sparse convs are rulebook gather/GEMM programs (_GatherConv) —
the COO pattern is host data so the neighbor rulebook is built host-side and
cached per pattern (the reference builds its rulebook in-kernel,
paddle/phi/kernels/sparse/conv_kernel.h); the value path is one traced
gather + one MXU matmul, jit-safe and O(nnz·K), never densified.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...nn import functional as F
from ...nn.layer.layers import Layer
from ...ops.dispatch import apply
from ...tensor.tensor import Tensor
from .. import SparseCooTensor, SparseCsrTensor, mask_as

__all__ = ["ReLU", "ReLU6", "LeakyReLU", "Softmax", "BatchNorm", "SyncBatchNorm",
           "Conv2D", "Conv3D", "SubmConv2D", "SubmConv3D", "MaxPool3D"]


def _map_values(x, fn, name):
    vals = apply(fn, x._values, op_name=name)
    if isinstance(x, SparseCooTensor):
        return SparseCooTensor(x._indices, vals, x.shape)
    return SparseCsrTensor(x._crows, x._cols, vals, x.shape)


class ReLU(Layer):
    def forward(self, x):
        return _map_values(x, lambda v: jnp.maximum(v, 0), "sparse_relu")


class ReLU6(Layer):
    def forward(self, x):
        return _map_values(x, lambda v: jnp.clip(v, 0, 6), "sparse_relu6")


class LeakyReLU(Layer):
    def __init__(self, negative_slope=0.01):
        super().__init__()
        self.negative_slope = negative_slope

    def forward(self, x):
        a = self.negative_slope
        return _map_values(x, lambda v: jnp.where(v >= 0, v, a * v), "sparse_leaky_relu")


class Softmax(Layer):
    """Row-wise softmax over the stored nonzeros (CSR semantics)."""

    def __init__(self, axis=-1):
        super().__init__()
        if axis != -1:
            raise NotImplementedError("sparse Softmax supports axis=-1")

    def forward(self, x):
        import numpy as np

        import jax

        csr = x if isinstance(x, SparseCsrTensor) else x.to_sparse_csr()
        rows = jnp.asarray(csr._rows(), jnp.int32)
        nrows = csr.shape[0]

        def f(v):
            rmax = jax.ops.segment_max(v, rows, num_segments=nrows)
            e = jnp.exp(v - rmax[rows])
            denom = jax.ops.segment_sum(e, rows, num_segments=nrows)
            return e / denom[rows]

        vals = apply(f, csr._values, op_name="sparse_softmax")
        out = SparseCsrTensor(csr._crows, csr._cols, vals, csr.shape)
        return out if isinstance(x, SparseCsrTensor) else out.to_sparse_coo()


class BatchNorm(Layer):
    """BatchNorm over the trailing feature axis of COO values (NDHWC-style
    sparse input: values are [nnz, C])."""

    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NDHWC", name=None):
        super().__init__()
        from ...nn.layer.norm import BatchNorm1D

        self._bn = BatchNorm1D(num_features, momentum=momentum, epsilon=epsilon,
                               weight_attr=weight_attr, bias_attr=bias_attr)

    def forward(self, x):
        out_vals = self._bn(x._values)
        return SparseCooTensor(x._indices, out_vals, x.shape)


class SyncBatchNorm(BatchNorm):
    """Under SPMD the (sharded) batch statistics are computed by the same
    program on every device — GSPMD inserts the cross-device reductions, so
    sync-BN is plain BN here (reference: sync_batch_norm distributed op)."""


class _GatherConv(Layer):
    """Rulebook sparse conv, TPU-shaped (reference analog: the rulebook
    construction + gather/GEMM/scatter of
    /root/reference/paddle/phi/kernels/sparse/conv_kernel.h and gpu/conv.cu).

    The COO *pattern* (indices) is host data — static under jit, exactly like
    the reference builds its rulebook on the host/stream before the GEMMs.
    The neighbor table (out-site × kernel-offset → input-slot or miss) is
    built once per pattern with numpy sort/searchsorted and cached; the
    VALUE path is one traced gather + one dense [nnz·K, Cin]×[K·Cin, Cout]
    matmul on the MXU — fully jit-safe (no host nonzero,
    no densify) and scaling with nnz, not spatial volume.
    """

    def __init__(self, nd, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, subm=False, bias_attr=None,
                 data_format=None):
        super().__init__()
        import numpy as np

        def tup(v):
            return tuple(v) if isinstance(v, (list, tuple)) else (v,) * nd

        self._nd = nd
        self._subm = subm
        self._ks = tup(kernel_size)
        self._stride = tup(stride)
        self._padding = tup(padding)
        self._dilation = tup(dilation)
        self._groups = groups
        self._cin, self._cout = in_channels, out_channels
        if in_channels % groups or out_channels % groups:
            raise ValueError("channels must divide groups")
        if subm and any(s != 1 for s in self._stride):
            raise ValueError("SubmConv requires stride 1 (pattern-preserving)")
        K = int(np.prod(self._ks))
        # weight layout mirrors the dense conv: [Cout, Cin/groups, *ks]
        self.weight = self.create_parameter(
            [out_channels, in_channels // groups, *self._ks])
        self.bias = (None if bias_attr is False
                     else self.create_parameter([out_channels], is_bias=True))
        self._K = K
        # bounded LRU: point-cloud workloads present a fresh pattern every
        # batch; unbounded caching would leak one rulebook per pattern
        from collections import OrderedDict

        self._rulebook_cache = OrderedDict()
        self._rulebook_cache_max = 16

    # ------------------------------------------------------- rulebook (host)
    def _offsets(self):
        import itertools

        import numpy as np

        return np.array(list(itertools.product(*[range(k) for k in self._ks])),
                        np.int64)  # [K, nd]

    def _encode(self, coords, spatial):
        """coords [M, nd+1] (batch + spatial) -> scalar keys."""
        import numpy as np

        key = coords[:, 0].astype(np.int64)
        for d in range(self._nd):
            key = key * int(spatial[d] + 1) + coords[:, 1 + d]
        return key

    def _rulebook(self, idx, in_shape):
        """(out_indices [nd+1, nnz_out], nbr [nnz_out, K] input slot or nnz)."""
        import numpy as np

        key_cache = (idx.tobytes(), tuple(in_shape))
        hit = self._rulebook_cache.get(key_cache)
        if hit is not None:
            self._rulebook_cache.move_to_end(key_cache)
            return hit
        spatial_in = in_shape[1:-1]
        nnz = idx.shape[1]
        coords = idx.T.astype(np.int64)  # [nnz, nd+1]
        offs = self._offsets()           # [K, nd]
        st = np.array(self._stride)
        pd = np.array(self._padding)
        dl = np.array(self._dilation)
        spatial_out = [
            (spatial_in[d] + 2 * self._padding[d]
             - self._dilation[d] * (self._ks[d] - 1) - 1) // self._stride[d] + 1
            for d in range(self._nd)
        ]

        if self._subm:
            out_coords = coords
            spatial_out = list(spatial_in)
        else:
            # candidate out sites: every (input site, kernel offset) pair
            # that lands on a stride point in range
            c = coords[:, None, 1:] + pd - offs[None, :, :] * dl  # [nnz,K,nd]
            ok = (c % st == 0).all(-1)
            o = c // st
            ok &= ((o >= 0) & (o < np.array(spatial_out))).all(-1)
            b = np.broadcast_to(coords[:, None, :1], o.shape[:2] + (1,))
            cand = np.concatenate([b, o], -1)[ok]  # [M, nd+1]
            if cand.shape[0] == 0:
                out_coords = np.zeros((0, self._nd + 1), np.int64)
            else:
                keys = self._encode(cand, spatial_out)
                _, first = np.unique(keys, return_index=True)
                out_coords = cand[np.sort(first)]

        # neighbor table: out site o, offset k -> input slot of coordinate
        # o*stride - padding + k*dilation (miss -> nnz, the zero row)
        in_keys = self._encode(coords, spatial_in)
        order = np.argsort(in_keys)
        sorted_keys = in_keys[order]
        nnz_out = out_coords.shape[0]
        nbr = np.full((max(nnz_out, 1), self._K), nnz, np.int64)
        for k in range(self._K):
            q = out_coords[:, 1:] * st - pd + offs[k] * dl
            valid = ((q >= 0) & (q < np.array(spatial_in))).all(-1)
            qfull = np.concatenate([out_coords[:, :1], q], -1)
            qkeys = self._encode(qfull, spatial_in)
            pos = np.searchsorted(sorted_keys, qkeys)
            pos = np.clip(pos, 0, nnz - 1)
            found = valid & (sorted_keys[pos] == qkeys) if nnz else np.zeros_like(valid)
            slot = np.where(found, order[pos], nnz)
            nbr[:nnz_out, k] = slot
        result = (out_coords.T, nbr[:nnz_out], spatial_out)
        self._rulebook_cache[key_cache] = result
        if len(self._rulebook_cache) > self._rulebook_cache_max:
            self._rulebook_cache.popitem(last=False)
        return result

    # --------------------------------------------------------------- forward
    def forward(self, x: SparseCooTensor):
        import numpy as np

        idx = x._indices_host
        if idx is None:  # pattern itself traced: not supported (static COO)
            raise ValueError(
                "sparse conv needs a host-known COO pattern; construct the "
                "SparseCooTensor from concrete indices (values may be traced)")
        out_idx, nbr, spatial_out = self._rulebook(idx, list(x.shape))
        nnz, K, g = idx.shape[1], self._K, self._groups
        cin_g = self._cin // g
        cout_g = self._cout // g
        nbr_j = jnp.asarray(nbr)

        def f(v, w, *rest):
            # v: [nnz, Cin]; zero row at slot nnz catches misses
            vpad = jnp.concatenate([v, jnp.zeros((1, v.shape[-1]), v.dtype)])
            gath = vpad[nbr_j]                              # [nnz_out, K, Cin]
            # [Cout, Cin/g, *ks] -> [K, g, Cin/g, Cout/g]
            wk = w.reshape(g, cout_g, cin_g, K)
            wk = jnp.transpose(wk, (3, 0, 2, 1))
            gg = gath.reshape(gath.shape[0], K, g, cin_g)
            out = jnp.einsum("nkgc,kgco->ngo", gg, wk.astype(v.dtype))
            out = out.reshape(gath.shape[0], self._cout)
            if rest:
                out = out + rest[0].astype(out.dtype)
            return out

        args = (x._values, self.weight) + ((self.bias,) if self.bias is not None else ())
        vals = apply(f, *args, op_name="subm_conv" if self._subm else "sparse_conv")
        out_shape = [x.shape[0], *spatial_out, self._cout]
        return SparseCooTensor(out_idx, vals, out_shape)


class Conv2D(_GatherConv):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", weight_attr=None,
                 bias_attr=None, data_format="NHWC"):
        super().__init__(2, in_channels, out_channels, kernel_size,
                         stride, padding, dilation, groups, subm=False,
                         bias_attr=bias_attr)


class Conv3D(_GatherConv):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", weight_attr=None,
                 bias_attr=None, data_format="NDHWC"):
        super().__init__(3, in_channels, out_channels, kernel_size,
                         stride, padding, dilation, groups, subm=False,
                         bias_attr=bias_attr)


class SubmConv2D(_GatherConv):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", key=None,
                 weight_attr=None, bias_attr=None, data_format="NHWC"):
        super().__init__(2, in_channels, out_channels, kernel_size,
                         stride, padding, dilation, groups, subm=True,
                         bias_attr=bias_attr)


class SubmConv3D(_GatherConv):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", key=None,
                 weight_attr=None, bias_attr=None, data_format="NDHWC"):
        super().__init__(3, in_channels, out_channels, kernel_size,
                         stride, padding, dilation, groups, subm=True,
                         bias_attr=bias_attr)


class MaxPool3D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, ceil_mode=False,
                 return_mask=False, data_format="NDHWC", name=None):
        super().__init__()
        self.kernel_size = kernel_size
        self.stride = stride or kernel_size
        self.padding = padding

    def forward(self, x: SparseCooTensor):
        dense = x.to_dense()  # [N, D, H, W, C]
        from ...tensor import linalg as _la

        nchw = _la.transpose(dense, [0, 4, 1, 2, 3])
        out = F.max_pool3d(nchw, self.kernel_size, self.stride, self.padding)
        out = _la.transpose(out, [0, 2, 3, 4, 1])
        import numpy as np

        arr = np.asarray(out._value)
        idx = np.stack(np.nonzero((arr != 0).any(-1)))
        vals = apply(lambda d: d[tuple(jnp.asarray(idx))], out, op_name="sparse_pool_gather")
        return SparseCooTensor(idx, vals, list(out.shape))
