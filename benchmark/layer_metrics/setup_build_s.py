"""Model, engine or train-step build: ``model.init`` + ``engine.init`` +
``frontend.init`` + ``train_step.init`` of the program's set-up ledger, less the
compiles of eager operations inside them, which ``setup_trace_s`` and
``setup_backend_s`` count."""
from benchmark.harness import setup_ledger


def read(run):
    return setup_ledger.reading(run, "build_s")
