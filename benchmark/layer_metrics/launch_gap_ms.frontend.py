"""Frontend: the part of ``launch_gap_ms`` under ``frontend.step`` /
``frontend.dispatch`` / ``frontend.deliver`` and outside ``engine.step``:
shedding, dispatch, token callbacks, journal records, finished requests."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.launch_gap_ms(run, ("frontend.",))
