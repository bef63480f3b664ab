"""paddle_tpu.jit (parity: python/paddle/jit)."""
from . import trace_state  # noqa: F401
from .compile_cache import use_compile_cache  # noqa: F401
from .api import InputSpec, StaticFunction, TrainStep, ignore_module, not_to_static, to_static  # noqa: F401
from .serialization import load, save  # noqa: F401

from .serialization import LoadedLayer as TranslatedLayer  # noqa: F401  (paddle name)


def enable_to_static(flag: bool = True):
    """Globally toggle to_static compilation (parity: jit.enable_to_static).
    When off, StaticFunction calls fall through to eager."""
    from . import api

    api._to_static_enabled = bool(flag)


def set_code_level(level=100, also_to_stdout=False):
    pass  # dy2static transformed-code dumping: no AST transform stage exists


def set_verbosity(level=0, also_to_stdout=False):
    pass
