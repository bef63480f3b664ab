"""Engine scheduler: device idle time a launch, in ms: the idle seconds of the
traced window (before the first module event, between module events, after
the last) over the launches of ``jit_step`` / ``jit_mega`` / ``jit_mixed`` /
``jit_spec_verify``. Its parts by the program span that covers each instant
are ``launch_gap_ms.schedule`` / ``.launch`` / ``.harvest`` / ``.frontend``;
what is left is the caller's (the generator, the benchmark's hooks) and the
self time of ``engine.step``."""
from benchmark.harness.program_trace import launch_gap_ms as read  # noqa: F401
