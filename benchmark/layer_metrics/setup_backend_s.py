"""Programs, compiler: ``backend_s`` summed over the compile ledger's rows of the
set-up, the small programs of eager operations included: XLA compiling, or the
persistent cache read back.  Prints the five dearest programs by name, each
with ``cache_hit``, and the sum of the rest."""
from benchmark.harness import report, setup_ledger


def read(run):
    value = setup_ledger.reading(run, "backend_s")
    if value is not None:
        report.note(setup_programs=setup_ledger.dearest(run))
    return value
