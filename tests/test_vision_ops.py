"""vision.ops + new model-family tests (SURVEY §2.3 vision row)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.vision import models, ops


RNG = np.random.RandomState(31)


def _v(t):
    return np.asarray(t._value)


class TestNMS:
    def test_greedy_nms(self):
        boxes = np.array([
            [0, 0, 10, 10], [1, 1, 11, 11],  # overlap pair
            [50, 50, 60, 60],
        ], np.float32)
        scores = np.array([0.9, 0.8, 0.7], np.float32)
        keep = _v(ops.nms(P.to_tensor(boxes), 0.5, P.to_tensor(scores)))
        assert keep.tolist() == [0, 2]

    def test_nms_category_aware(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        cats = np.array([0, 1])
        keep = _v(ops.nms(P.to_tensor(boxes), 0.5, P.to_tensor(scores),
                          category_idxs=P.to_tensor(cats), categories=[0, 1]))
        assert sorted(keep.tolist()) == [0, 1]  # different classes both kept

    def test_matrix_nms(self):
        bboxes = np.array([[[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]]], np.float32)
        scores = np.array([[[0.9, 0.85, 0.7]]], np.float32)  # [N, cls, boxes]
        scores = np.concatenate([np.zeros_like(scores), scores], axis=1)  # bg + 1 class
        out, rois_num = ops.matrix_nms(P.to_tensor(bboxes), P.to_tensor(scores),
                                       score_threshold=0.1, post_threshold=0.1,
                                       nms_top_k=10, keep_top_k=10)
        assert _v(out).shape[1] == 6
        assert int(_v(rois_num)[0]) >= 2


class TestRoIOps:
    def test_roi_align_uniform_feature(self):
        # constant feature map -> every aligned bin equals the constant
        feat = np.full((1, 3, 16, 16), 2.5, np.float32)
        boxes = np.array([[2.0, 2.0, 10.0, 10.0]], np.float32)
        out = _v(ops.roi_align(P.to_tensor(feat), P.to_tensor(boxes),
                               P.to_tensor(np.array([1])), output_size=4))
        assert out.shape == (1, 3, 4, 4)
        np.testing.assert_allclose(out, 2.5, rtol=1e-5)

    def test_roi_align_gradient(self):
        feat = P.to_tensor(RNG.randn(1, 2, 8, 8).astype(np.float32))
        feat.stop_gradient = False
        boxes = P.to_tensor(np.array([[1.0, 1.0, 6.0, 6.0]], np.float32))
        out = ops.roi_align(feat, boxes, P.to_tensor(np.array([1])), 2)
        P.sum(out).backward()
        assert feat.grad is not None and np.isfinite(_v(feat.grad)).all()

    def test_roi_pool_max(self):
        feat = np.zeros((1, 1, 8, 8), np.float32)
        feat[0, 0, 3, 3] = 7.0
        out = _v(ops.roi_pool(P.to_tensor(feat), P.to_tensor(np.array([[0.0, 0.0, 7.0, 7.0]], np.float32)),
                              P.to_tensor(np.array([1])), output_size=1))
        np.testing.assert_allclose(out.reshape(-1), [7.0])

    def test_psroi_pool_shapes(self):
        feat = P.to_tensor(RNG.randn(1, 2 * 2 * 4, 8, 8).astype(np.float32))
        boxes = P.to_tensor(np.array([[0.0, 0.0, 7.0, 7.0]], np.float32))
        out = ops.psroi_pool(feat, boxes, P.to_tensor(np.array([1])), 2)
        assert list(out.shape) == [1, 4, 2, 2]


class TestBoxOps:
    def test_box_coder_roundtrip(self):
        priors = np.array([[10, 10, 30, 30], [5, 5, 15, 25]], np.float32)
        targets = np.array([[12, 11, 28, 33]], np.float32)
        enc = ops.box_coder(P.to_tensor(priors), [1.0, 1.0, 1.0, 1.0],
                            P.to_tensor(targets), "encode_center_size")
        dec = ops.box_coder(P.to_tensor(priors), [1.0, 1.0, 1.0, 1.0],
                            enc, "decode_center_size", axis=0)
        np.testing.assert_allclose(_v(dec)[0, 0], targets[0], rtol=1e-4, atol=1e-3)

    def test_prior_box(self):
        feat = P.to_tensor(np.zeros((1, 8, 4, 4), np.float32))
        img = P.to_tensor(np.zeros((1, 3, 32, 32), np.float32))
        boxes, variances = ops.prior_box(feat, img, min_sizes=[8.0], max_sizes=[16.0],
                                         aspect_ratios=[2.0], clip=True)
        assert _v(boxes).shape[:2] == (4, 4)
        assert _v(boxes).min() >= 0 and _v(boxes).max() <= 1
        assert _v(variances).shape == _v(boxes).shape

    def test_yolo_box_shapes(self):
        cls = 3
        na = 2
        x = P.to_tensor(RNG.randn(1, na * (5 + cls), 4, 4).astype(np.float32))
        boxes, scores = ops.yolo_box(x, P.to_tensor(np.array([[64, 64]], np.int32)),
                                     anchors=[10, 14, 23, 27], class_num=cls,
                                     conf_thresh=0.0, downsample_ratio=16)
        assert _v(boxes).shape == (1, na * 16, 4)
        assert _v(scores).shape == (1, na * 16, cls)

    def test_distribute_fpn(self):
        rois = np.array([[0, 0, 16, 16], [0, 0, 200, 200]], np.float32)
        outs, restore, nums = ops.distribute_fpn_proposals(
            P.to_tensor(rois), 2, 5, 4, 224)
        assert sum(int(_v(n)[0]) for n in nums) == 2
        assert sorted(_v(restore).tolist()) == [0, 1]


class TestDeformConv:
    def test_zero_offset_matches_conv(self):
        import paddle_tpu.nn.functional as F

        x = P.to_tensor(RNG.randn(1, 2, 8, 8).astype(np.float32))
        w = P.to_tensor(RNG.randn(4, 2, 3, 3).astype(np.float32))
        offset = P.to_tensor(np.zeros((1, 2 * 3 * 3, 8, 8), np.float32))
        out = ops.deform_conv2d(x, offset, w, padding=1)
        ref = F.conv2d(x, w, padding=1)
        np.testing.assert_allclose(_v(out), _v(ref), rtol=1e-3, atol=1e-4)

    def test_layer_and_grad(self):
        layer = ops.DeformConv2D(2, 3, 3, padding=1)
        x = P.to_tensor(RNG.randn(1, 2, 6, 6).astype(np.float32))
        x.stop_gradient = False
        offset = P.to_tensor(0.1 * RNG.randn(1, 18, 6, 6).astype(np.float32))
        offset.stop_gradient = False
        out = layer(x, offset)
        assert list(out.shape) == [1, 3, 6, 6]
        P.sum(out).backward()
        assert x.grad is not None and offset.grad is not None
        assert layer.weight.grad is not None


class TestNewModels:
    @pytest.mark.parametrize("factory,ch", [
        (lambda: models.alexnet(num_classes=10), 224),
        (lambda: models.squeezenet1_1(num_classes=10), 64),
        (lambda: models.mobilenet_v1(scale=0.25, num_classes=10), 64),
        (lambda: models.mobilenet_v3_small(scale=0.5, num_classes=10), 64),
        (lambda: models.shufflenet_v2_x0_25(num_classes=10), 64),
        (lambda: models.densenet121(num_classes=10), 64),
    ], ids=["alexnet", "squeezenet", "mbv1", "mbv3", "shufflev2", "densenet"])
    def test_forward_shape(self, factory, ch):
        net = factory()
        net.eval()
        x = P.to_tensor(RNG.randn(2, 3, ch, ch).astype(np.float32))
        # ONE traced forward: op by op the zoo's hundreds of layers are
        # hundreds of compiles (densenet 822 programs, 35 s against 2)
        out = P.jit.to_static(net)(x)
        assert list(out.shape) == [2, 10]


class TestReviewRegressions:
    def test_diagonal_scatter_swapped_axes(self):
        x = np.zeros((3, 3), np.float32)
        out = _v(P.diagonal_scatter(P.to_tensor(x), P.to_tensor(np.array([1.0, 2.0])),
                                    offset=1, axis1=1, axis2=0))
        # dim1=1, dim2=0: the sub-diagonal positions (1,0), (2,1)
        assert out[1, 0] == 1.0 and out[2, 1] == 2.0
        assert out[0, 1] == 0.0

    def test_bernoulli_detaches_grad(self):
        from paddle_tpu.tensor import bernoulli_

        w = P.to_tensor(np.ones(4, np.float32))
        w.stop_gradient = False
        x = w * 3.0
        bernoulli_(x, p=0.5)
        P.sum(x).backward()
        assert w.grad is None  # random fill severed the path

    def test_nms_large_coordinates_cross_class(self):
        boxes = np.array([[4100, 4100, 4110, 4110], [4, 4, 14, 14]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        cats = np.array([0, 1])
        keep = _v(ops.nms(P.to_tensor(boxes), 0.5, P.to_tensor(scores),
                          category_idxs=P.to_tensor(cats), categories=[0, 1]))
        assert sorted(keep.tolist()) == [0, 1]

    def test_matrix_nms_empty_scalar_return(self):
        bboxes = np.array([[[0, 0, 10, 10]]], np.float32)
        scores = np.zeros((1, 2, 1), np.float32)  # all below threshold
        out = ops.matrix_nms(P.to_tensor(bboxes), P.to_tensor(scores),
                             score_threshold=0.5, post_threshold=0.5,
                             nms_top_k=5, keep_top_k=5,
                             return_index=False, return_rois_num=False)
        assert hasattr(out, "shape")  # bare Tensor, not a tuple

    def test_googlenet_inception(self):
        net = models.googlenet(num_classes=7)
        net.eval()
        out, aux1, aux2 = P.jit.to_static(net)(
            P.to_tensor(RNG.randn(1, 3, 64, 64).astype(np.float32)))
        assert list(out.shape) == [1, 7]
        inc = models.inception_v3(num_classes=7)
        inc.eval()
        out = P.jit.to_static(inc)(P.to_tensor(RNG.randn(1, 3, 128, 128).astype(np.float32)))
        assert list(out.shape) == [1, 7]
