"""Engine scheduler: tokens a decode-scan launch emitted, on average
(``megastep_tokens`` / ``megasteps``): how full the K x slots grid ran."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("megasteps"):
        return None
    return c["megastep_tokens"] / c["megasteps"]
