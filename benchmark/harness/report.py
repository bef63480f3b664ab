"""Arithmetic on samples and the one result line."""
import json
import math


def percentile(values, q):
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values):
    return percentile(values, 50)


def note(**kv):
    """An earlier line of the output: what a reader wants beside the result
    (medians, sample counts, each number compared with its limit)."""
    print(json.dumps(kv), flush=True)


class Checks:
    """Every number compared, beside its limit. ``correct`` is all of them."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, *, at_least=False):
        value = float(value)
        ok = math.isfinite(value) and (value >= limit if at_least else value <= limit)
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "must_be": ">=" if at_least else "<=", "ok": ok})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self):
        for r in self.rows:
            note(**r)


def result_line(*, correct, attempted, failed, metrics, units, device, breakdown=None):
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
