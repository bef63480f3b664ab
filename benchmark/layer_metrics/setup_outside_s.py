"""The harness and jax: ``setup_s`` less the other five ``setup_*_s``: jax's start
and ``jax.devices()``, the family's ``make_weights`` and ``assign``, anything the
program has not named.  It should be small; where it is not, a span is missing."""
from benchmark.harness import setup_ledger


def read(run):
    return setup_ledger.reading(run, "outside_s")
