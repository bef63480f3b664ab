"""to_static graph-break fallback (reference analog: SOT's
resume-eager at untraceable bytecode, opcode_executor.py:1594)."""
import warnings

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import nn


class BranchyNet(nn.Layer):
    """Data-dependent Python branching + .numpy() inside forward."""

    def __init__(self):
        super().__init__()
        self.a = nn.Linear(8, 8)
        self.b = nn.Linear(8, 8)

    def forward(self, x):
        # .numpy() on a traced value -> graph break
        if float(np.asarray(x.numpy()).sum()) > 0:
            return self.a(x)
        return self.b(x)


def test_graph_break_falls_back_and_trains():
    P.seed(0)
    net = BranchyNet()
    st = P.jit.to_static(net)
    x = P.to_tensor(np.abs(np.random.RandomState(0).randn(4, 8)).astype(np.float32))
    y = P.randn([4, 8])
    opt = P.optimizer.SGD(0.1, parameters=net.parameters())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        losses = []
        for _ in range(8):
            loss = P.nn.functional.mse_loss(st(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
    assert any("graph break" in str(x.message) for x in w)
    assert losses[-1] < losses[0]  # it still TRAINS through the fallback
    # the failure is cached: the second call did not attempt a re-trace
    assert len(st._fallback_keys) == 1
    assert not st._cache


def test_full_graph_mode_raises():
    net = BranchyNet()
    st = P.jit.to_static(net, full_graph=True)
    x = P.randn([4, 8])
    with pytest.raises(Exception):
        st(x)


def test_traceable_function_still_compiles():
    net = nn.Linear(8, 4)
    st = P.jit.to_static(net)
    x = P.randn([2, 8])
    out = st(x)
    np.testing.assert_allclose(out.numpy(), net(x).numpy(), rtol=1e-5)
    assert st._cache and not st._fallback_keys


def test_mixed_signatures_break_independently():
    """One signature breaks (batch whose .numpy branch), another compiles."""
    calls = []

    def f(x, flag=False):
        if flag:
            _ = float(np.asarray(x.numpy()).sum())  # break only when flag
        calls.append(1)
        return x * 2

    st = P.jit.to_static(f)
    a = st(P.randn([3]))
    assert a.shape == [3]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        b = st(P.randn([3]), True)
    assert b.shape == [3]
    assert len(st._fallback_keys) == 1 and len(st._cache) == 1


class MidBreakNet(nn.Layer):
    """A .numpy() host read in the MIDDLE of the model: prefix and suffix
    must become separate compiled segments."""

    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        h = self.fc1(x)
        scale = float(np.asarray(h.numpy()).mean())  # host read mid-model
        h = h * (1.0 + 0.0 * scale) + scale * 0.0  # uses the host value
        return self.fc2(h)


class MidBreakScaledNet(nn.Layer):
    """Variant where the host-read value actually changes the math."""

    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        h = self.fc1(x)
        s = float(np.asarray(h.numpy()).std()) + 1.0
        return self.fc2(h / s)


def test_mid_function_break_two_segments(tmp_path):
    """One .numpy() mid-model yields exactly TWO compiled segments (counted
    via FLAGS_dump_hlo artifacts), and the loss matches full-eager."""
    P.seed(1)
    net = MidBreakScaledNet()
    st = P.jit.to_static(net)
    x = P.to_tensor(np.random.RandomState(3).randn(4, 8).astype(np.float32))

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out1 = st(x)  # first call: trace fails -> segmented execution
    assert st.last_segment_count == 2

    # parity with full eager (fused segment vs per-op rounding: rtol 1e-4)
    ref = net(x)
    np.testing.assert_allclose(np.asarray(out1.numpy()), np.asarray(ref.numpy()),
                               rtol=1e-4, atol=1e-6)

    # FLAGS_dump_hlo artifact count: exactly two segment programs dumped
    P.set_flags({"FLAGS_dump_hlo": str(tmp_path)})
    try:
        st(x)
        import os

        seg_dumps = [f for f in os.listdir(tmp_path)
                     if "seg" in f and f.endswith(".stablehlo.txt")]
        assert len(seg_dumps) == 2, seg_dumps
    finally:
        P.set_flags({"FLAGS_dump_hlo": ""})


def test_mid_break_trains_matching_eager():
    """Backward through segmented execution: grads equal full-eager grads."""
    P.seed(2)
    net = MidBreakScaledNet()
    st = P.jit.to_static(net)
    x = P.to_tensor(np.random.RandomState(4).randn(4, 8).astype(np.float32))
    y = P.randn([4, 4])

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        loss_seg = P.nn.functional.mse_loss(st(x), y)
    loss_seg.backward()
    g_seg = np.asarray(net.fc1.weight.grad.numpy()).copy()
    net.clear_gradients()

    loss_eager = P.nn.functional.mse_loss(net(x), y)
    loss_eager.backward()
    g_eager = np.asarray(net.fc1.weight.grad.numpy())
    np.testing.assert_allclose(float(loss_seg.numpy()), float(loss_eager.numpy()),
                               rtol=1e-5)
    np.testing.assert_allclose(g_seg, g_eager, rtol=1e-4, atol=1e-6)

    # it trains
    opt = P.optimizer.SGD(0.1, parameters=net.parameters())
    losses = []
    for _ in range(8):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            loss = P.nn.functional.mse_loss(st(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]


def test_segment_guards_are_per_segment():
    """Guard semantics: a repeat call reuses every segment executable; new
    data re-specializes ONLY the segment that folded the host-read scalar
    (a jaxpr literal — the SOT value-guard analog), while the prefix
    segment's executable is reused."""
    from paddle_tpu.jit import lazy_segments

    P.seed(5)
    net = MidBreakScaledNet()
    st = P.jit.to_static(net)
    from paddle_tpu.autograd import tape

    x1 = P.to_tensor(np.random.RandomState(7).randn(4, 8).astype(np.float32))
    with tape.no_grad():  # inference path = the jaxpr-keyed executable cache
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            st(x1)
        n_after_first = len(lazy_segments._segment_cache)
        assert n_after_first >= 2  # both segments cached
        # same data again: full reuse, no new executables
        st(x1)
        assert len(lazy_segments._segment_cache) == n_after_first
        # new data: the prefix segment is value-independent and reused; only
        # the suffix (host scalar baked as a literal) re-specializes
        st(P.to_tensor(np.random.RandomState(8).randn(4, 8).astype(np.float32)))
    assert len(lazy_segments._segment_cache) == n_after_first + 1


class InplaceBreakNet(nn.Layer):
    """In-place op after a mid-model host read (review regression: the
    adopted pending value must alias through the segment flush)."""

    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 8)

    def forward(self, x):
        h = self.fc1(x)
        _ = float(np.asarray(h.numpy()).mean())  # host read -> flush
        h2 = h * 2.0
        h2.add_(P.ones([8]))  # in-place on a PENDING tensor
        return h2 * 0.5


def test_inplace_op_in_segmented_mode_matches_eager():
    P.seed(6)
    net = InplaceBreakNet()
    st = P.jit.to_static(net)
    x = P.to_tensor(np.random.RandomState(9).randn(4, 8).astype(np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = st(x)
    ref = net(x)
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref.numpy()),
                               rtol=1e-4, atol=1e-6)
