"""Local-path pretrained-weight loading mechanics."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.vision.models import resnet18


def test_pretrained_from_local_npz(tmp_path):
    P.seed(0)
    donor = resnet18(num_classes=10)
    arrays = {k: np.asarray(v._value) for k, v in donor.state_dict().items()}
    path = tmp_path / "resnet18.npz"
    np.savez(path, **arrays)

    P.seed(99)  # different init — the load must overwrite it
    model = resnet18(pretrained=str(path), num_classes=10)
    for k, v in model.state_dict().items():
        np.testing.assert_allclose(np.asarray(v._value), arrays[k], rtol=1e-6,
                                   err_msg=k)


def test_pretrained_home_env(tmp_path, monkeypatch):
    P.seed(0)
    donor = resnet18(num_classes=10)
    arrays = {k: np.asarray(v._value) for k, v in donor.state_dict().items()}
    np.savez(tmp_path / "resnet18.npz", **arrays)
    monkeypatch.setenv("PADDLE_TPU_PRETRAINED_HOME", str(tmp_path))
    model = resnet18(pretrained=True, num_classes=10)
    k0 = next(iter(arrays))
    np.testing.assert_allclose(np.asarray(model.state_dict()[k0]._value), arrays[k0])


def test_missing_weights_helpful_error(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PRETRAINED_HOME", str(tmp_path / "nope"))
    with pytest.raises(RuntimeError, match="pretrained weights"):
        resnet18(pretrained=True)
