"""Train step: median device duration of the ``jit_step`` module events."""
from benchmark.harness import report, xplane


def read(run):
    if run.get("trace") is None:
        return None
    durs = xplane.module_durations(run["trace"], ("jit_step",))
    return 1000.0 * report.median(durs) if durs else None
