"""Golden-value tests: the nn/functional op tail vs torch CPU references
(the tail had only smoke asserts; reference's own OpTest
compares against authoritative numerics, test/legacy_test/op_test.py:2119).

torch (CPU build) is part of the image; it provides independent ground truth
for exactly the ops whose reference implementations are CUDA kernels we
re-derived from scratch.
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import paddle_tpu as P  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402

RNG = np.random.RandomState(0)


def _t(x):
    return P.to_tensor(np.asarray(x, np.float32))


def test_grid_sample_bilinear_golden():
    x = RNG.randn(2, 3, 8, 8).astype(np.float32)
    grid = (RNG.rand(2, 5, 5, 2).astype(np.float32) * 2 - 1)
    ours = F.grid_sample(_t(x), _t(grid), mode="bilinear",
                         padding_mode="zeros", align_corners=False).numpy()
    ref = torch.nn.functional.grid_sample(
        torch.tensor(x), torch.tensor(grid), mode="bilinear",
        padding_mode="zeros", align_corners=False).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_affine_grid_golden():
    theta = RNG.randn(2, 2, 3).astype(np.float32)
    ours = F.affine_grid(_t(theta), [2, 3, 6, 7], align_corners=True).numpy()
    ref = torch.nn.functional.affine_grid(
        torch.tensor(theta), [2, 3, 6, 7], align_corners=True).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_pairwise_distance_golden():
    a = RNG.randn(4, 16).astype(np.float32)
    b = RNG.randn(4, 16).astype(np.float32)
    ours = F.pairwise_distance(_t(a), _t(b), p=2.0).numpy()
    ref = torch.nn.functional.pairwise_distance(
        torch.tensor(a), torch.tensor(b), p=2.0).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_gaussian_nll_loss_golden():
    x = RNG.randn(6, 3).astype(np.float32)
    y = RNG.randn(6, 3).astype(np.float32)
    var = np.abs(RNG.randn(6, 3)).astype(np.float32) + 0.1
    ours = F.gaussian_nll_loss(_t(x), _t(y), _t(var), full=True,
                               reduction="mean").numpy()
    ref = torch.nn.functional.gaussian_nll_loss(
        torch.tensor(x), torch.tensor(y), torch.tensor(var), full=True,
        reduction="mean").numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_multi_margin_loss_golden():
    x = RNG.randn(5, 7).astype(np.float32)
    y = RNG.randint(0, 7, (5,)).astype(np.int64)
    ours = F.multi_margin_loss(_t(x), P.to_tensor(y), p=1, margin=1.0,
                               reduction="mean").numpy()
    ref = torch.nn.functional.multi_margin_loss(
        torch.tensor(x), torch.tensor(y), p=1, margin=1.0,
        reduction="mean").numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_triplet_margin_with_distance_golden():
    a, p_, n = (RNG.randn(4, 8).astype(np.float32) for _ in range(3))
    ours = F.triplet_margin_with_distance_loss(
        _t(a), _t(p_), _t(n), margin=1.0, reduction="mean").numpy()
    ref = torch.nn.functional.triplet_margin_with_distance_loss(
        torch.tensor(a), torch.tensor(p_), torch.tensor(n), margin=1.0,
        reduction="mean").numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_max_unpool2d_golden():
    x = RNG.randn(1, 2, 8, 8).astype(np.float32)
    tx = torch.tensor(x)
    pooled_t, idx_t = torch.nn.functional.max_pool2d(tx, 2, return_indices=True)
    from paddle_tpu.nn.functional.extra import max_pool2d_with_index

    pooled_p, idx_p = max_pool2d_with_index(_t(x), 2)
    np.testing.assert_allclose(pooled_p.numpy(), pooled_t.numpy(), rtol=1e-5)
    np.testing.assert_allclose(idx_p.numpy().astype(np.int64), idx_t.numpy())
    ours = F.max_unpool2d(pooled_p, idx_p, 2, output_size=[8, 8]).numpy()
    ref = torch.nn.functional.max_unpool2d(pooled_t, idx_t, 2, output_size=[8, 8]).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


def test_lp_pool2d_golden():
    x = np.abs(RNG.randn(2, 3, 8, 8)).astype(np.float32)
    ours = F.lp_pool2d(_t(x), norm_type=2.0, kernel_size=2).numpy()
    ref = torch.nn.functional.lp_pool2d(torch.tensor(x), norm_type=2.0,
                                        kernel_size=2).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_rnnt_loss_golden():
    torchaudio = pytest.importorskip("torchaudio")
    B, T, U, V = 2, 6, 4, 5
    logits = RNG.randn(B, T, U + 1, V).astype(np.float32)
    labels = RNG.randint(1, V, (B, U)).astype(np.int32)
    in_len = np.full((B,), T, np.int32)
    lab_len = np.full((B,), U, np.int32)
    ours = F.rnnt_loss(_t(logits), P.to_tensor(labels), P.to_tensor(in_len),
                       P.to_tensor(lab_len), blank=0, fastemit_lambda=0.0,
                       reduction="mean").numpy()
    ref = torchaudio.functional.rnnt_loss(
        torch.tensor(logits), torch.tensor(labels), torch.tensor(in_len),
        torch.tensor(lab_len), blank=0, reduction="mean").numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-3, atol=1e-4)


def test_hinge_embedding_and_softmargin_golden():
    x = RNG.randn(6, 4).astype(np.float32)
    y = np.sign(RNG.randn(6, 4)).astype(np.float32)
    ours = F.hinge_embedding_loss(_t(x), _t(y), margin=1.0, reduction="mean").numpy()
    ref = torch.nn.functional.hinge_embedding_loss(
        torch.tensor(x), torch.tensor(y), margin=1.0, reduction="mean").numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)
    ours2 = F.soft_margin_loss(_t(x), _t(y), reduction="mean").numpy()
    ref2 = torch.nn.functional.soft_margin_loss(
        torch.tensor(x), torch.tensor(y), reduction="mean").numpy()
    np.testing.assert_allclose(ours2, ref2, rtol=1e-4, atol=1e-5)


def test_pixel_shuffle_unshuffle_golden():
    x = RNG.randn(2, 8, 4, 4).astype(np.float32)
    ours = F.pixel_shuffle(_t(x), 2).numpy()
    ref = torch.nn.functional.pixel_shuffle(torch.tensor(x), 2).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-6)
    ours2 = F.pixel_unshuffle(_t(ref), 2).numpy()
    ref2 = torch.nn.functional.pixel_unshuffle(torch.tensor(ref), 2).numpy()
    np.testing.assert_allclose(ours2, ref2, rtol=1e-6)


def test_cosine_embedding_loss_golden():
    a = RNG.randn(5, 9).astype(np.float32)
    b = RNG.randn(5, 9).astype(np.float32)
    y = np.sign(RNG.randn(5)).astype(np.float32)
    ours = F.cosine_embedding_loss(_t(a), _t(b), _t(y), margin=0.2,
                                   reduction="mean").numpy()
    ref = torch.nn.functional.cosine_embedding_loss(
        torch.tensor(a), torch.tensor(b), torch.tensor(y), margin=0.2,
        reduction="mean").numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)
