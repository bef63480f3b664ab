"""Programs, host: ``trace_s + lower_s`` summed over the compile ledger's rows of
the set-up: the Python that tracing the layers' loops and lowering them costs,
whether the program is then compiled or read from the cache."""
from benchmark.harness import setup_ledger


def read(run):
    return setup_ledger.reading(run, "trace_s")
