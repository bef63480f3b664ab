"""Tokens of the optimizer steps that completed inside the window, over the
window, all chips together; the window ends on a host read of the last loss."""


def read(run):
    return run["tokens_in_window"] / run["window_s"]
