"""Engine / train step, running: from the first ``program.acquire`` to the
window's opening, less every compile and build inside: the warm-up launches and
the traffic's ramp, executing, and each new program's first enqueue."""
from benchmark.harness import setup_ledger


def read(run):
    return setup_ledger.reading(run, "run_s")
