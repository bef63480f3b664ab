"""Device: share of the traced window in which no operation ran on it."""
from benchmark.harness.engine_counters import idle_share as read  # noqa: F401
