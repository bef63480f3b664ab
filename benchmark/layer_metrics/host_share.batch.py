"""Engine scheduler: share of the engine's step time spent outside the
compiled call (schedule + harvest), from ``phase_seconds`` over the window."""
from benchmark.harness.engine_counters import host_share as read  # noqa: F401
