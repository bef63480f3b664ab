"""Engine scheduler: the part of ``launch_gap_ms`` under ``engine.admit`` and
``engine.schedule``: admission and building the launch's host arrays."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.launch_gap_ms(run, ("engine.admit", "engine.schedule"))
