"""Detection-tail op tests: yolo_loss vs a numpy oracle
of the published YOLOv3 loss, generate_proposals decode/NMS behavior,
decode_jpeg roundtrip, deform_conv2d groups>1."""
import io

import numpy as np
import pytest
from scipy.special import expit as _sigmoid  # scipy ships with the env

import paddle_tpu as P
from paddle_tpu.vision.ops import (
    decode_jpeg,
    deform_conv2d,
    generate_proposals,
    yolo_loss,
)

pytestmark = pytest.mark.quick


def _np_sce(logit, label):
    p = _sigmoid(logit)
    return -(label * np.log(p) + (1 - label) * np.log(1 - p))


def _np_iou_xywh(a, b):
    """a [P,4], b [Q,4] center xywh -> [P,Q] IoU, clipped like the kernel."""
    def corners(x):
        return (x[:, 0] - x[:, 2] / 2, x[:, 0] + x[:, 2] / 2,
                x[:, 1] - x[:, 3] / 2, x[:, 1] + x[:, 3] / 2)

    l1, r1, t1, b1 = corners(a)
    l2, r2, t2, b2 = corners(b)
    iw = np.maximum(np.minimum(r1[:, None], r2) - np.maximum(l1[:, None], l2), 0)
    ih = np.maximum(np.minimum(b1[:, None], b2) - np.maximum(t1[:, None], t2), 0)
    inter = iw * ih
    union = ((r1 - l1) * (b1 - t1))[:, None] + (r2 - l2) * (b2 - t2) - inter
    return inter / np.maximum(union, 1e-10)


def yolo_loss_oracle(x, gtb, gtl, gts, anchors, mask, C, ignore_thresh,
                     ds, smooth, sxy):
    """Published YOLOv3 loss, written loop-wise for clarity (semantics:
    reference yolo_loss op docs + test oracle behavior)."""
    N, _, h, w = x.shape
    B = gtb.shape[1]
    M = len(mask)
    inp = ds * h
    xr = x.reshape(N, M, 5 + C, h, w).transpose(0, 1, 3, 4, 2).astype(np.float64)
    man = np.array([(anchors[2 * m] / inp, anchors[2 * m + 1] / inp)
                    for m in mask])
    alla = np.array([(anchors[2 * i] / inp, anchors[2 * i + 1] / inp)
                     for i in range(len(anchors) // 2)])
    sm = min(1.0 / C, 1.0 / 40)
    pos_l, neg_l = (1 - sm, sm) if smooth else (1.0, 0.0)
    bias = -0.5 * (sxy - 1.0)
    total = np.zeros(N)
    for i in range(N):
        # decoded preds for the ignore decision
        pb = np.zeros((M, h, w, 4))
        for a in range(M):
            for r in range(h):
                for c in range(w):
                    pb[a, r, c, 0] = (c + _sigmoid(xr[i, a, r, c, 0]) * sxy + bias) / w
                    pb[a, r, c, 1] = (r + _sigmoid(xr[i, a, r, c, 1]) * sxy + bias) / h
                    pb[a, r, c, 2] = np.exp(xr[i, a, r, c, 2]) * man[a, 0]
                    pb[a, r, c, 3] = np.exp(xr[i, a, r, c, 3]) * man[a, 1]
        pb = pb.reshape(-1, 4)
        ious = _np_iou_xywh(pb, gtb[i])
        obj = np.where(ious.max(1) > ignore_thresh, -1.0, 0.0)
        for j in range(B):
            gw, gh = gtb[i, j, 2], gtb[i, j, 3]
            if gw + gh <= 0:
                continue
            wh = np.array([[0, 0, gw, gh]])
            ab = np.concatenate([np.zeros_like(alla), alla], 1)
            best = int(np.argmax(_np_iou_xywh(wh, ab)[0]))
            if best not in mask:
                continue
            a = mask.index(best)
            gi = int(gtb[i, j, 0] * w)
            gj = int(gtb[i, j, 1] * h)
            tx = gtb[i, j, 0] * w - gi
            ty = gtb[i, j, 1] * h - gj
            tw = np.log(gw / man[a, 0])
            th = np.log(gh / man[a, 1])
            sc = (2.0 - gw * gh) * gts[i, j]
            p = xr[i, a, gj, gi]
            total[i] += (_np_sce(p[0], tx) + _np_sce(p[1], ty)
                         + abs(p[2] - tw) + abs(p[3] - th)) * sc
            for cc in range(C):
                total[i] += _np_sce(p[5 + cc],
                                    pos_l if cc == gtl[i, j] else neg_l) * gts[i, j]
            obj[a * h * w + gj * w + gi] = gts[i, j]
        po = xr[i, :, :, :, 4].reshape(-1)
        for t in range(M * h * w):
            if obj[t] > 0:
                total[i] += _np_sce(po[t], 1.0) * obj[t]
            elif obj[t] == 0:
                total[i] += _np_sce(po[t], 0.0)
    return total


class TestYoloLoss:
    @pytest.mark.parametrize("smooth,sxy,with_score",
                             [(True, 1.0, False), (False, 1.2, True)])
    def test_matches_oracle(self, smooth, sxy, with_score):
        rng = np.random.RandomState(7)
        N, h, w, C = 2, 6, 6, 4
        anchors = [10, 13, 16, 30, 33, 23]
        mask = [0, 1, 2]
        M = len(mask)
        x = rng.randn(N, M * (5 + C), h, w).astype(np.float32) * 0.4
        B = 3
        gxy = rng.uniform(0.1, 0.9, (N, B, 2))
        gwh = rng.uniform(0.05, 0.4, (N, B, 2))
        gtb = np.concatenate([gxy, gwh], -1).astype(np.float32)
        gtb[0, 2] = 0  # an empty gt slot
        gtl = rng.randint(0, C, (N, B)).astype(np.int32)
        gts = (rng.uniform(0.5, 1.0, (N, B)).astype(np.float32)
               if with_score else np.ones((N, B), np.float32))
        out = yolo_loss(P.to_tensor(x), P.to_tensor(gtb), P.to_tensor(gtl),
                        anchors, mask, C, ignore_thresh=0.55,
                        downsample_ratio=32,
                        gt_score=P.to_tensor(gts) if with_score else None,
                        use_label_smooth=smooth, scale_x_y=sxy)
        ref = yolo_loss_oracle(x, gtb, gtl, gts, anchors, mask, C, 0.55, 32,
                               smooth, sxy)
        np.testing.assert_allclose(np.asarray(out.numpy()), ref,
                                   rtol=2e-4, atol=2e-4)

    def test_gradients_flow(self):
        rng = np.random.RandomState(1)
        N, h, w, C = 1, 4, 4, 3
        x = P.to_tensor(rng.randn(N, 3 * (5 + C), h, w).astype(np.float32) * 0.3)
        x.stop_gradient = False
        gtb = P.to_tensor(np.array([[[0.5, 0.5, 0.3, 0.4],
                                     [0.2, 0.7, 0.1, 0.2]]], np.float32))
        gtl = P.to_tensor(np.array([[1, 2]], np.int32))
        loss = yolo_loss(x, gtb, gtl, [10, 13, 16, 30, 33, 23], [0, 1, 2], C,
                         0.7, 32)
        P.sum(loss).backward()
        g = np.asarray(x.grad.numpy())
        assert np.isfinite(g).all() and np.abs(g).sum() > 0


class TestGenerateProposals:
    def test_identity_deltas_recover_anchors(self):
        """Zero deltas with unit variances must return the (clipped) anchors
        ranked by score, NMS de-duplicating overlaps."""
        H = W = 2
        A = 2
        # anchors [H, W, A, 4] — well separated, inside the image
        an = np.zeros((H, W, A, 4), np.float32)
        k = 0
        for r in range(H):
            for c in range(W):
                for a in range(A):
                    x0 = 10 * k
                    an[r, c, a] = [x0, x0, x0 + 6 + a, x0 + 6 + a]
                    k += 1
        va = np.ones_like(an)
        sc = np.arange(A * H * W, dtype=np.float32).reshape(A, H, W) / 10
        dl = np.zeros((1, 4 * A, H, W), np.float32)
        rois, probs, nums = generate_proposals(
            P.to_tensor(sc[None]), P.to_tensor(dl),
            P.to_tensor(np.array([[100.0, 100.0]], np.float32)),
            P.to_tensor(an), P.to_tensor(va),
            pre_nms_top_n=10, post_nms_top_n=10, nms_thresh=0.5,
            min_size=1.0, return_rois_num=True)
        r = np.asarray(rois.numpy())
        p = np.asarray(probs.numpy())
        assert int(np.asarray(nums.numpy())[0]) == r.shape[0] == 8
        assert (p[:-1, 0] >= p[1:, 0]).all()  # score-descending
        # every anchor survives (they don't overlap), recovered exactly
        got = {tuple(b) for b in r.astype(int).tolist()}
        want = {tuple(b) for b in an.reshape(-1, 4).astype(int).tolist()}
        assert got == want

    def test_decode_clip_minsize_and_nms(self):
        H = W = 1
        A = 3
        an = np.array([[[[0, 0, 10, 10],
                         [0, 0, 10, 10],
                         [40, 40, 41, 41]]]], np.float32).reshape(H, W, A, 4)
        va = np.full((H, W, A, 4), 0.5, np.float32)
        sc = np.array([[[[0.9]], [[0.8]], [[0.7]]]], np.float32)  # [1,A,1,1]
        dl = np.zeros((1, 4 * A, H, W), np.float32)
        dl[0, 4 * 2 + 2] = -8.0  # shrink the third anchor below min_size
        rois, probs = generate_proposals(
            P.to_tensor(sc), P.to_tensor(dl),
            P.to_tensor(np.array([[50.0, 50.0]], np.float32)),
            P.to_tensor(an), P.to_tensor(va),
            nms_thresh=0.5, min_size=2.0)
        r = np.asarray(rois.numpy())
        # duplicate anchor NMS'd away, tiny box filtered: one roi remains
        assert r.shape[0] == 1
        np.testing.assert_allclose(r[0], [0, 0, 10, 10], atol=1e-4)


class TestDecodeJpeg:
    def test_roundtrip(self):
        from PIL import Image

        # smooth gradient: random noise is adversarial for a lossy codec
        yy, xx = np.mgrid[0:16, 0:20]
        img = np.stack([yy * 8, xx * 6, (yy + xx) * 4], -1).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=95)
        data = np.frombuffer(buf.getvalue(), np.uint8)
        out = decode_jpeg(P.to_tensor(data))
        arr = np.asarray(out.numpy())
        assert arr.shape == (3, 16, 20)
        # lossy codec: close, not exact
        assert np.abs(arr.astype(int) - img.transpose(2, 0, 1).astype(int)).mean() < 12
        gray = decode_jpeg(P.to_tensor(data), mode="gray")
        assert np.asarray(gray.numpy()).shape == (1, 16, 20)


class TestDeformGroups:
    def test_groups_match_split_computation(self):
        rng = np.random.RandomState(2)
        N, C, H, W, O, k, G = 1, 4, 6, 6, 6, 3, 2
        x = rng.randn(N, C, H, W).astype(np.float32)
        wgt = rng.randn(O, C // G, k, k).astype(np.float32)
        off = rng.randn(N, 2 * k * k, H, W).astype(np.float32) * 0.3
        out = deform_conv2d(P.to_tensor(x), P.to_tensor(off),
                            P.to_tensor(wgt), padding=1, groups=G)
        out = np.asarray(out.numpy())
        # oracle: run each group as its own groups=1 conv on its channels
        for g in range(G):
            xg = x[:, g * (C // G):(g + 1) * (C // G)]
            wg = wgt[g * (O // G):(g + 1) * (O // G)]
            og = deform_conv2d(P.to_tensor(xg), P.to_tensor(off),
                               P.to_tensor(wg), padding=1, groups=1)
            np.testing.assert_allclose(
                out[:, g * (O // G):(g + 1) * (O // G)],
                np.asarray(og.numpy()), rtol=1e-4, atol=1e-4)
