"""Readings of the engine's host counters, as deltas over the window."""


def host_share(run):
    """(schedule + harvest) / (schedule + execute + harvest), in percent.
    ``execute`` is the compiled call with its device sync, so the rest is
    host work during which the engine launches nothing."""
    c = run.get("counters") or {}
    if "phase_execute" not in c:
        return None
    host = c["phase_schedule"] + c["phase_harvest"]
    total = host + c["phase_execute"]
    return 100.0 * host / total if total > 0 else None


def idle_share(run):
    """1 - device busy / traced window, in percent, from the device trace."""
    from benchmark.harness import xplane

    if run.get("trace") is None:
        return None
    busy, window = xplane.busy_and_window(run["trace"])
    return 100.0 * (1.0 - busy / window)
