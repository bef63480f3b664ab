"""Process start to the first instant of the window: imports, weights, model,
engine or train step, compile or cache read, warm-up, ramp. Host clock."""


def read(run):
    return run["setup_s"]
