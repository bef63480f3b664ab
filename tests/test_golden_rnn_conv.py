"""Golden-value tests: recurrent + conv + norm stacks vs torch CPU
(the structurally complex layers where a
re-derived implementation can silently diverge)."""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import paddle_tpu as P  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402

RNG = np.random.RandomState(0)


def _copy_rnn_weights(ours, theirs, layers, bidirectional):
    """torch L(STM/GRU/RNN) weight names match ours structurally."""
    for layer in range(layers):
        for d in range(2 if bidirectional else 1):
            suffix = f"_l{layer}{'_reverse' if d else ''}"
            our_suffix = f"_l{layer}{'_rev' if d else ''}"
            for kind in ("weight_ih", "weight_hh", "bias_ih", "bias_hh"):
                src = np.asarray(ours._parameters[f"{kind}{our_suffix}"]._value)
                getattr(theirs, f"{kind}{suffix}").data = torch.tensor(src)


def _rnn_names(module):
    # our ScanRNN registers weight_ih_l0 style names
    return sorted(module._parameters)


@pytest.mark.parametrize("mode", ["LSTM", "GRU", "SimpleRNN"])
@pytest.mark.parametrize("bidirectional", [False, True])
def test_rnn_family_matches_torch(mode, bidirectional):
    P.seed(0)
    E, H, L = 6, 8, 2
    direction = "bidirect" if bidirectional else "forward"
    ours = {"LSTM": nn.LSTM, "GRU": nn.GRU, "SimpleRNN": nn.SimpleRNN}[mode](
        E, H, num_layers=L, direction=direction)
    tcls = {"LSTM": torch.nn.LSTM, "GRU": torch.nn.GRU,
            "SimpleRNN": torch.nn.RNN}[mode]
    theirs = tcls(E, H, num_layers=L, bidirectional=bidirectional,
                  batch_first=True)
    _copy_rnn_weights(ours, theirs, L, bidirectional)

    x = RNG.randn(3, 5, E).astype(np.float32)
    out_p = ours(P.to_tensor(x))
    out_t = theirs(torch.tensor(x))
    o_p = out_p[0].numpy()
    o_t = out_t[0].detach().numpy()
    np.testing.assert_allclose(o_p, o_t, rtol=1e-4, atol=1e-5)
    if mode == "LSTM":
        h_p, c_p = out_p[1]
        h_t, c_t = out_t[1]
        np.testing.assert_allclose(h_p.numpy(), h_t.detach().numpy(), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(c_p.numpy(), c_t.detach().numpy(), rtol=1e-4, atol=1e-5)
    else:
        np.testing.assert_allclose(out_p[1].numpy(), out_t[1].detach().numpy(),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("stride,padding,dilation,groups", [
    (1, 0, 1, 1), (2, 1, 1, 1), (1, 2, 2, 1), (1, 1, 1, 2),
])
def test_conv2d_matches_torch(stride, padding, dilation, groups):
    x = RNG.randn(2, 4, 11, 11).astype(np.float32)
    w = RNG.randn(6, 4 // groups, 3, 3).astype(np.float32)
    b = RNG.randn(6).astype(np.float32)
    ours = F.conv2d(P.to_tensor(x), P.to_tensor(w), P.to_tensor(b),
                    stride=stride, padding=padding, dilation=dilation,
                    groups=groups).numpy()
    ref = torch.nn.functional.conv2d(
        torch.tensor(x), torch.tensor(w), torch.tensor(b), stride=stride,
        padding=padding, dilation=dilation, groups=groups).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-3, atol=1e-4)


def test_conv2d_transpose_matches_torch():
    x = RNG.randn(2, 4, 7, 7).astype(np.float32)
    w = RNG.randn(4, 5, 3, 3).astype(np.float32)
    ours = F.conv2d_transpose(P.to_tensor(x), P.to_tensor(w), stride=2,
                              padding=1, output_padding=1).numpy()
    ref = torch.nn.functional.conv_transpose2d(
        torch.tensor(x), torch.tensor(w), stride=2, padding=1,
        output_padding=1).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-3, atol=1e-4)


def test_batchnorm_train_and_eval_match_torch():
    x = RNG.randn(4, 3, 6, 6).astype(np.float32)
    ours = nn.BatchNorm2D(3, momentum=0.9)
    theirs = torch.nn.BatchNorm2d(3, momentum=0.1)  # torch momentum = 1-ours
    ours.train()
    theirs.train()
    for _ in range(3):
        o_p = ours(P.to_tensor(x)).numpy()
        o_t = theirs(torch.tensor(x)).detach().numpy()
    np.testing.assert_allclose(o_p, o_t, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(ours._buffers["_mean"]._value),
        theirs.running_mean.numpy(), rtol=1e-4, atol=1e-5)
    # paddle (and this framework) track the BIASED batch variance in the
    # running stats (phi/kernels/cpu/batch_norm_kernel.cc:157); torch tracks
    # the unbiased one — correct by n/(n-1) for the comparison
    n = 4 * 6 * 6
    decay = 0.9 ** 3  # surviving share of the running-var init (1.0)
    ours_unbiased = decay + (np.asarray(ours._buffers["_variance"]._value)
                             - decay) * n / (n - 1)
    np.testing.assert_allclose(ours_unbiased, theirs.running_var.numpy(),
                               rtol=1e-4, atol=1e-4)
    ours.eval()
    theirs.eval()
    # eval normalizes by the tracked stats; sync torch's (unbiased-tracked)
    # running_var to our paddle-parity biased one so the normalization math
    # itself is what's compared
    theirs.running_var.data = torch.tensor(
        np.asarray(ours._buffers["_variance"]._value))
    np.testing.assert_allclose(ours(P.to_tensor(x)).numpy(),
                               theirs(torch.tensor(x)).detach().numpy(),
                               rtol=1e-4, atol=1e-4)


def test_layernorm_groupnorm_match_torch():
    x = RNG.randn(3, 8, 5).astype(np.float32)
    ln = nn.LayerNorm([8, 5])
    tln = torch.nn.LayerNorm([8, 5])
    tln.weight.data = torch.tensor(np.asarray(ln.weight._value))
    tln.bias.data = torch.tensor(np.asarray(ln.bias._value))
    np.testing.assert_allclose(ln(P.to_tensor(x)).numpy(),
                               tln(torch.tensor(x)).detach().numpy(),
                               rtol=1e-4, atol=1e-5)
    xg = RNG.randn(2, 6, 4, 4).astype(np.float32)
    gn = nn.GroupNorm(3, 6)
    tgn = torch.nn.GroupNorm(3, 6)
    tgn.weight.data = torch.tensor(np.asarray(gn.weight._value))
    tgn.bias.data = torch.tensor(np.asarray(gn.bias._value))
    np.testing.assert_allclose(gn(P.to_tensor(xg)).numpy(),
                               tgn(torch.tensor(xg)).detach().numpy(),
                               rtol=1e-4, atol=1e-5)


def test_embedding_and_ctc_loss_match_torch():
    table = RNG.randn(10, 4).astype(np.float32)
    ids = RNG.randint(0, 10, (3, 5)).astype(np.int64)
    ours = F.embedding(P.to_tensor(ids), P.to_tensor(table)).numpy()
    ref = torch.nn.functional.embedding(torch.tensor(ids), torch.tensor(table)).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-6)

    # CTC: [T, B, V] log-probs
    T, B, V, S = 8, 2, 5, 3
    logits = RNG.randn(T, B, V).astype(np.float32)
    labels = RNG.randint(1, V, (B, S)).astype(np.int32)
    in_len = np.full((B,), T, np.int64)
    lab_len = np.full((B,), S, np.int64)
    lp = torch.tensor(logits).log_softmax(-1)
    ref = torch.nn.functional.ctc_loss(
        lp, torch.tensor(labels), torch.tensor(in_len), torch.tensor(lab_len),
        blank=0, reduction="mean").numpy()
    ours = F.ctc_loss(P.to_tensor(logits),  # paddle layout [T, N, C]
                      P.to_tensor(labels), P.to_tensor(in_len.astype(np.int64)),
                      P.to_tensor(lab_len.astype(np.int64)), blank=0,
                      reduction="mean").numpy()
    np.testing.assert_allclose(float(ours), float(ref), rtol=1e-3, atol=1e-4)
