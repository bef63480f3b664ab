"""Device: ``memory_stats()["peak_bytes_in_use"]`` on the fullest chip, read
after the window and before the reference touches the device."""


def read(run):
    peak = run.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
