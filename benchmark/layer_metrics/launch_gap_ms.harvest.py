"""Engine scheduler: the part of ``launch_gap_ms`` under ``engine.wait`` and
``engine.harvest``: the end of the blocking reads after the device has
finished, and the loop over rows that commits tokens and retires requests."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.launch_gap_ms(run, ("engine.wait", "engine.harvest"))
