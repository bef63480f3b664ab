"""paddle_tpu — a TPU-native deep learning framework with PaddlePaddle's
capability surface, built on JAX/XLA/Pallas/pjit idioms.

Top-level namespace mirrors ``paddle``: tensors, ops, nn, optimizer, amp, io,
jit, distributed, vision, etc. See SURVEY.md for the reference layer map this
rebuild tracks.
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os
import sys as _sys
import time as _time

# ``setup.import`` (profiler/__init__.py): this file, first line to last
_import_t0, _jax_loaded = _time.perf_counter(), "jax" in _sys.modules

import jax as _jax

# Paddle-parity numerics: float32 ops mean float32. This environment's default
# lets XLA truncate f32 matmul operands to bf16; we pin HIGHEST and make low
# precision an explicit choice (bf16 dtype / amp), exactly like the reference's
# fp32-by-default kernels. Override with FLAGS_matmul_precision=default|high.
if "FLAGS_matmul_precision" not in _os.environ:
    _jax.config.update("jax_default_matmul_precision", "highest")
else:
    _prec = _os.environ["FLAGS_matmul_precision"]
    if _prec != "default":
        _jax.config.update("jax_default_matmul_precision", _prec)

# framework primitives
from .framework import (  # noqa: F401
    bfloat16,
    bool_,
    float8_e4m3fn,
    float8_e5m2,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_flags,
    int8,
    int16,
    int32,
    int64,
    seed,
    set_default_dtype,
    set_flags,
    uint8,
)
from .framework import random as _random_mod  # noqa: F401
from .framework.dtype import dtype  # noqa: F401
from .framework.random import get_rng_state, set_rng_state  # noqa: F401

# tensor + ops (this import also patches Tensor methods)
from .tensor import Tensor  # noqa: F401
from .tensor import *  # noqa: F401,F403
from .tensor import creation as _creation  # noqa: F401

# autograd
from . import autograd  # noqa: F401
from .autograd import no_grad, enable_grad, set_grad_enabled, is_grad_enabled, grad  # noqa: F401

# device
from . import device  # noqa: F401
from .device import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    TPUPlace,
    get_device,
    set_device,
    is_compiled_with_cuda,
    is_compiled_with_rocm,
    is_compiled_with_xpu,
)

# subsystems (imported lazily-tolerant during bootstrap; all present by v0.1)
import importlib as _importlib

for _sub in ("nn", "optimizer", "metric", "amp", "io", "jit", "vision", "distributed",
             "models", "profiler", "hapi", "regularizer", "distribution", "fft",
             "sparse", "static", "quantization", "inference", "audio", "text",
             "callbacks", "incubate", "signal", "strings"):
    try:
        globals()[_sub] = _importlib.import_module(f".{_sub}", __name__)
    except ModuleNotFoundError as _e:
        if f"paddle_tpu.{_sub}" not in str(_e):
            raise

try:
    from .framework_io import load, save  # noqa: F401
except ModuleNotFoundError:
    pass

from .base.param_attr import ParamAttr  # noqa: F401
from .device import CUDAPinnedPlace  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401


class LazyGuard:
    """parity: paddle.LazyGuard — parameters created inside the guard are
    ABSTRACT (a shape and a type, ``jax.ShapeDtypeStruct``): nothing is
    drawn and nothing is placed on the device until a value is assigned
    (``param._value = array``).  For a model whose weights arrive from
    elsewhere and whose own copy would not fit beside them."""

    def __enter__(self):
        from .nn.layer import layers

        layers._lazy_parameters += 1
        return self

    def __exit__(self, *exc):
        from .nn.layer import layers

        layers._lazy_parameters -= 1
        return False


bool = bool_  # noqa: A001  (paddle exports the dtype as paddle.bool)


def tolist(x):
    """paddle.tolist parity."""
    return x.tolist() if hasattr(x, "tolist") else list(x)

try:
    from .hapi import Model, summary  # noqa: F401
except ModuleNotFoundError:
    pass

# ---------------------------------------------------------- execution mode
# dynamic (eager-over-XLA) by default; enable_static() switches the dispatch
# chokepoint into lazy Program capture (see paddle_tpu.static)
_dynamic_mode = True


def in_dynamic_mode() -> bool:
    return _dynamic_mode


def enable_static():
    global _dynamic_mode
    _dynamic_mode = False
    from .ops import dispatch as _dispatch

    _dispatch._static_capture = True


def disable_static(place=None):
    global _dynamic_mode
    _dynamic_mode = True
    from .ops import dispatch as _dispatch

    _dispatch._static_capture = False


def is_grad_enabled_():
    return is_grad_enabled()


profiler.SETUP.record("setup.import", _import_t0, _time.perf_counter() - _import_t0,
                      jax_loaded=_jax_loaded)
