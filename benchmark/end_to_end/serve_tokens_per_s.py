"""Output tokens whose ``on_token`` stamp falls inside the window, over the
window: what a batch user pays for. Host clock."""


def read(run):
    t0, t1 = run["t_open"], run["t_close"]
    n = sum(1 for r in run["requests"] for s in r.stamps if t0 <= s <= t1)
    return n / (t1 - t0)
