"""Training feed: a new batch of uniform random token ids every step, from a
seeded host generator, rows all different.

Parameters (the traffic file): batch (sequences a step, all chips together),
seq (tokens a sequence), loss_every (steps between host reads of the loss)."""
import numpy as np


def batches(traffic, seed, vocab):
    """An endless iterator of [batch, seq] int32 arrays."""
    rng = np.random.default_rng([int(seed), 15485863])
    shape = (int(traffic["batch"]), int(traffic["seq"]))
    while True:
        yield rng.integers(0, vocab, shape, dtype=np.int32)
