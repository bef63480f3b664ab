"""Engine scheduler: the part of ``launch_gap_ms`` under ``engine.launch``:
the transfers of the launch's arrays and the dispatch of the jitted call."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.launch_gap_ms(run, ("engine.launch",))
