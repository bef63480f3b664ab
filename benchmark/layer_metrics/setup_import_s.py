"""Process: the seconds of ``import paddle_tpu`` (the ``setup.import`` row of the
program's set-up ledger, with jax's own import inside it where its ``jax_loaded``
is false), less what jax compiled meanwhile."""
from benchmark.harness import setup_ledger


def read(run):
    return setup_ledger.reading(run, "import_s")
