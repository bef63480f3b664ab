"""paddle_tpu.amp (parity: python/paddle/amp)."""
from . import amp_lists  # noqa: F401
from .auto_cast import amp_guard, amp_state, auto_cast, decorate, is_auto_cast_enabled  # noqa: F401
from .grad_scaler import GradScaler  # noqa: F401
from . import debugging  # noqa: F401


def is_bfloat16_supported(device=None):
    """TPUs are bf16-native; CPU XLA also computes bf16."""
    return True


def is_float16_supported(device=None):
    import jax

    return jax.default_backend() in ("tpu", "gpu")
