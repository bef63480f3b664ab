#!/usr/bin/env python
"""Perf regression gate (reference analog: tools/ci_op_benchmark.sh +
check_op_benchmark_result.py — CI fails when a benchmark regresses vs the
recorded baseline).

Four checks; the first two run against the PREVIOUS round's recordings:

1. Headline: the newest BENCH_r*.json's ``vs_baseline`` ratio must not drop
   more than --tolerance (default 10%), and the pinned workload must not
   drift.
2. Ladder (r6, ISSUE #1): EVERY rung of the newest BENCH_LADDER_r*.json is
   compared against the same rung in the previous round within the
   per-rung tolerance recorded in tools/ladder_tolerances.json. Direction
   comes from the unit (``ms``-like units: lower is better; throughput
   units: higher is better). A rung that VANISHES from the latest round
   fails (a deleted rung could hide a regression); a new rung passes with
   a note. This is what keeps schedule wins (e.g. the r6 branch-free
   interleaved pipeline) and slow drifts (the ~4-7% BERT creep flagged in
   r5) from silently decaying.
3. Cross-rung (r16, ISSUE 16): bounds declared in ``CROSS_RUNG_BOUNDS``
   between rungs of the LATEST round — today, the saturated staggered-
   admission megastep rung must stay within 1.5x of the closed-batch
   megastep rung's host-round-trips-per-token (both deterministic counter
   ratios), or chunked prefill has stopped keeping the scan armed under
   open-loop load.
4. Absolute (r18, ISSUE 18): bounds declared in ``ABS_RUNG_BOUNDS`` on
   single rungs of the LATEST round — the tenant-isolation served share
   must stay in [0.40, 0.60] (0.5 is fair; drift in either direction is
   a fairness bug the one-sided delta check cannot catch), the
   warm-pool attach ratio must stay below 1.0 (a warm attach slower
   than a cold spawn means the pool is pure overhead), and the
   speculative-decoding forwards-per-token ratio (r19, ISSUE 19) must
   stay below 1.0 (at 1.0 no draft token was ever accepted and every
   verify launch was wasted work).

Run with no arguments from the repo root.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple


def _load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"perf-gate: skipping unreadable {path}: {e}")
        return None


def load_rounds(root: str):
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        data = _load_json(path)
        if data is None:
            continue
        # driver schema: the bench line lives under "parsed"
        if isinstance(data, dict) and "parsed" in data:
            data = data["parsed"]
        if isinstance(data, dict) and "vs_baseline" in data:
            out.append((int(m.group(1)), path, data))
    return sorted(out)


def load_ladders(root: str) -> List[Tuple[int, str, List[Dict]]]:
    """-> sorted [(round, path, rungs)]. Handles both recorded schemas:
    r3/r4 store a bare list of rungs, r5+ an object with a 'rungs' key."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_LADDER_r*.json")):
        m = re.search(r"BENCH_LADDER_r(\d+)\.json$", path)
        if not m:
            continue
        data = _load_json(path)
        if data is None:
            continue
        rungs = data.get("rungs") if isinstance(data, dict) else data
        if not isinstance(rungs, list):
            continue
        rungs = [r for r in rungs
                 if isinstance(r, dict) and "metric" in r and "value" in r]
        if rungs:
            out.append((int(m.group(1)), path, rungs))
    return sorted(out)


def load_tolerances(root: str) -> Dict:
    path = os.path.join(root, "tools", "ladder_tolerances.json")
    data = _load_json(path) if os.path.exists(path) else None
    if not isinstance(data, dict):
        data = {}
    return {"default": float(data.get("default", 0.10)),
            "rungs": dict(data.get("rungs", {}))}


def lower_is_better(rung: Dict) -> bool:
    unit = str(rung.get("unit", ""))
    return unit.startswith("ms") or unit.endswith("ms") or \
        str(rung.get("metric", "")).endswith("_ms")


# extra.* keys that define a rung's measurement CONFIG (not its outcome) —
# when one of these changes between rounds the values are not comparable
# and the rung re-baselines (loudly) instead of being gated numerically.
# 'method' is config too: rungs describe HOW the number was produced there
# (slope lengths, repeat counts, timing windows), and a changed estimator
# produces numbers on a different distribution — r8 measured the
# serving_mixed slope rung at 13.5k vs 24.2k tok/s on IDENTICAL code
# back-to-back, which forced its estimator to be hardened (and honestly
# re-baselined) rather than silently compared across methods
IDENTITY_KEYS = ("workload", "mesh", "backend", "host", "batch", "seq",
                 "img", "prompt", "new_tokens", "ring", "block_size",
                 "ctx_lengths", "num_micro", "replicas", "workers",
                 "num_requests", "rate_rps", "max_new_tokens", "method",
                 "shared_prefix_len")


def config_drift(prev: Dict, cur: Dict) -> List[str]:
    pe, ce = prev.get("extra") or {}, cur.get("extra") or {}
    # a key present in only ONE round is also drift: silently dropping
    # (or adding) e.g. 'mesh' must not let values measured on different
    # configs be compared as if identical
    return [k for k in IDENTITY_KEYS
            if (k in pe or k in ce) and pe.get(k) != ce.get(k)]


def check_headline(rounds, tolerance: float) -> int:
    if len(rounds) < 2:
        print(f"perf-gate: {len(rounds)} recorded headline round(s); "
              "nothing to compare — pass")
        return 0
    (pn, ppath, prev), (cn, cpath, cur) = rounds[-2], rounds[-1]
    pw = (prev.get("extra") or {}).get("workload")
    cw = (cur.get("extra") or {}).get("workload")
    if pw is not None and cw is not None and pw != cw:
        # the headline series is only meaningful on a pinned workload — a
        # drifted config is a FAILURE, not a skip
        print(f"perf-gate: FAIL — workload configs differ between r{pn} "
              f"{pw} and r{cn} {cw}; the headline metric must be measured "
              "on the pinned workload (set PADDLE_TPU_BENCH_* back, or "
              "consciously reset the baseline series)")
        return 1
    pv, cv = prev["vs_baseline"], cur["vs_baseline"]
    drop = (pv - cv) / pv if pv > 0 else 0.0
    print(f"perf-gate: headline r{pn} {pv:.4f} -> r{cn} {cv:.4f} "
          f"({'-' if drop > 0 else '+'}{abs(drop) * 100:.1f}%)")
    if drop > tolerance:
        print(f"perf-gate: FAIL — vs_baseline regressed more than "
              f"{tolerance * 100:.0f}% ({ppath} -> {cpath})")
        return 1
    return 0


def check_ladder(ladders, tolerances: Dict) -> int:
    if len(ladders) < 2:
        print(f"perf-gate: {len(ladders)} recorded ladder round(s); "
              "nothing to compare — pass")
        return 0
    (pn, ppath, prev), (cn, cpath, cur) = ladders[-2], ladders[-1]
    prev_by = {r["metric"]: r for r in prev}
    cur_by = {r["metric"]: r for r in cur}
    rc = 0
    for metric, pr in prev_by.items():
        entry = tolerances["rungs"].get(metric)
        if isinstance(entry, dict):
            # recorded form: {"tolerance": x, "lower_is_better": bool} —
            # an explicit direction beats the unit heuristic (which only
            # knows ms-like units)
            tol = float(entry.get("tolerance", tolerances["default"]))
            lower = entry.get("lower_is_better")
        else:
            tol = float(entry if entry is not None
                        else tolerances["default"])
            lower = None
        cr = cur_by.get(metric)
        if cr is None:
            print(f"perf-gate: FAIL — ladder rung '{metric}' present in "
                  f"r{pn} ({ppath}) but missing from r{cn} ({cpath}); a "
                  "vanished rung can hide a regression — re-measure it or "
                  "consciously retire it from BOTH rounds")
            rc = 1
            continue
        drifted = config_drift(pr, cr)
        if drifted:
            # forced config changes (e.g. the pp rung's mesh degrading on
            # an old-jax image) make the numbers incomparable: re-baseline
            # LOUDLY rather than fail forever or compare garbage — a
            # vanished rung still fails, so this cannot silently hide one
            pe, ce = pr.get("extra") or {}, cr.get("extra") or {}
            # .get: a drifted key may exist in only one round (that is
            # itself drift) — show '<absent>' instead of KeyError-ing
            changes = ", ".join(
                f"{k}: {pe.get(k, '<absent>')!r} -> {ce.get(k, '<absent>')!r}"
                for k in drifted)
            print(f"perf-gate: WARNING — rung '{metric}' measurement "
                  f"config changed between r{pn} and r{cn} ({changes}); "
                  "values not comparable, rung re-baselined this round")
            continue
        pv, cv = float(pr["value"]), float(cr["value"])
        if pv <= 0:
            print(f"perf-gate: rung '{metric}' r{pn} value {pv} not "
                  "comparable — skipped")
            continue
        if lower is None:
            lower = lower_is_better(pr)
        if lower:
            regression = (cv - pv) / pv
        else:
            regression = (pv - cv) / pv
        sign = "-" if regression > 0 else "+"
        print(f"perf-gate: rung {metric}: r{pn} {pv:g} -> r{cn} {cv:g} "
              f"({sign}{abs(regression) * 100:.1f}%, tol "
              f"{tol * 100:.0f}%)")
        if regression > tol:
            print(f"perf-gate: FAIL — '{metric}' regressed "
                  f"{regression * 100:.1f}% > {tol * 100:.0f}% tolerance "
                  f"({ppath} -> {cpath})")
            rc = 1
    for metric in cur_by:
        if metric not in prev_by:
            print(f"perf-gate: new ladder rung '{metric}' in r{cn} — no "
                  "prior round to gate against (recorded as baseline)")
    return rc


# cross-rung bounds WITHIN the latest round (ISSUE 16): unlike the
# round-over-round deltas above, these assert a relationship between two
# rungs measured together — the saturated open-admission megastep rung
# must stay within 1.5x of the closed-batch rung's host-round-trips-per-
# token, or chunked prefill has stopped keeping the scan armed under
# open-loop load.  Both rungs are deterministic counter ratios, so this
# check has no noise allowance beyond the factor itself.
CROSS_RUNG_BOUNDS = (
    ("serving_megastep_saturated_steps_per_token",
     "serving_megastep_steps_per_token", 1.5),
)

# absolute bounds WITHIN the latest round (ISSUE 18): some rungs have a
# contract the round-over-round delta cannot express.  The tenant-
# isolation share is a two-sided band — 0.5 is fair, and drift TOWARD
# 1.0 (steady starving bursty) is as much a bug as drift toward 0.0, but
# the directional tolerance check only fails one way.  The warm-pool
# ratio must stay under 1.0 outright: a warm attach slower than a cold
# spawn means the pool is pure overhead no matter how stable the number.
ABS_RUNG_BOUNDS = (
    ("serving_tenant_isolation_served_share", 0.40, 0.60),
    ("serving_warm_pool_attach_ratio", None, 1.0),
    # spec rung (ISSUE 19): forwards per spec-committed token is exactly
    # 1.0 when no draft token is ever accepted — a rung at or above 1.0
    # means every verify launch was pure overhead on a workload built to
    # accept, which the round-over-round delta check alone cannot catch
    # on the first round the rung appears
    ("serving_spec_forwards_per_token", None, 1.0),
    # data-plane rungs (r20, ISSUE 20): payload hop-bytes per pulled
    # byte is exactly 1.0 when every transferred block rides the direct
    # wire and exactly 2.0 when everything relays through the frontend;
    # anything at or above 1.5 means at least half the payload bytes
    # fell back off the data plane.  The frontend-relay-bytes rung is
    # 0.0 by contract (its round-over-round delta check auto-skips a
    # zero baseline, so the absolute bound IS the gate): a single
    # relayed byte on the direct path fails the round.
    ("serving_disagg_payload_hop_bytes", None, 1.4999),
    ("serving_disagg_frontend_relay_bytes", None, 0.0),
)


def check_cross_rungs(ladders) -> int:
    if not ladders:
        return 0
    cn, cpath, cur = ladders[-1]
    cur_by = {r["metric"]: r for r in cur}
    rc = 0
    for metric, ref, factor in CROSS_RUNG_BOUNDS:
        mr, rr = cur_by.get(metric), cur_by.get(ref)
        if mr is None or rr is None:
            continue  # pair not measured this round — nothing to bound
        mv, rv = float(mr["value"]), float(rr["value"])
        if rv <= 0:
            continue
        ratio = mv / rv
        print(f"perf-gate: cross-rung {metric} / {ref}: "
              f"{mv:g} / {rv:g} = {ratio:.3f}x (bound {factor:g}x)")
        if ratio > factor:
            print(f"perf-gate: FAIL — '{metric}' is {ratio:.2f}x '{ref}' "
                  f"in r{cn} ({cpath}), over the {factor:g}x bound")
            rc = 1
    return rc


def check_abs_rungs(ladders) -> int:
    if not ladders:
        return 0
    cn, cpath, cur = ladders[-1]
    cur_by = {r["metric"]: r for r in cur}
    rc = 0
    for metric, lo, hi in ABS_RUNG_BOUNDS:
        r = cur_by.get(metric)
        if r is None:
            continue  # rung not measured this round — nothing to bound
        v = float(r["value"])
        band = (f"[{lo:g}, {hi:g}]" if lo is not None
                else f"(-inf, {hi:g}]")
        print(f"perf-gate: abs-bound {metric}: {v:g} in {band}")
        if (lo is not None and v < lo) or v > hi:
            print(f"perf-gate: FAIL — '{metric}' = {v:g} in r{cn} "
                  f"({cpath}) is outside its absolute bound {band}")
            rc = 1
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="allowed fractional drop in the headline "
                         "vs_baseline (per-rung ladder tolerances come "
                         "from tools/ladder_tolerances.json)")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args(argv)

    rc = check_headline(load_rounds(args.root), args.tolerance)
    ladders = load_ladders(args.root)
    rc = check_ladder(ladders, load_tolerances(args.root)) or rc
    rc = check_cross_rungs(ladders) or rc
    rc = check_abs_rungs(ladders) or rc
    print("perf-gate: pass" if rc == 0 else "perf-gate: FAIL")
    return rc


if __name__ == "__main__":
    sys.exit(main())
