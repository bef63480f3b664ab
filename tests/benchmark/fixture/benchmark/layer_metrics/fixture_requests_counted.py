"""A per-layer metric added as a file: how many requests the window counted."""


def read(run):
    return len(run["counted"]) if "counted" in run else None
