"""Where ``setup_s`` went, as the program tells it: the six readings that the
``setup_*_s`` metrics report, from ``paddle_tpu.profiler.setup_report()``.

The report is read after the run, in the run's own process (the ledger is
process-wide, so it outlives the engine the driver has dropped), over the
interval ``benchmark.run`` counts ``setup_s`` on: ``t_open - setup_s`` (its
``T_PROCESS``) to ``t_open``, both ``time.perf_counter``'s, the ledger's clock.
Every second of a compile-ledger row goes to ``trace_s`` (tracing and lowering:
the host's Python) or ``backend_s`` (XLA compiling, or the persistent cache
read back), whichever stage it fell in, and is taken off that stage; so the
six add up to ``setup_s`` by construction, and ``outside_s`` is what the program
has no name for.  A program without the ledger (the parent of the PR that
brought it) gives ``None`` for all six."""

BUILD = ("model.init", "engine.init", "frontend.init", "train_step.init")
READINGS = ("import_s", "build_s", "trace_s", "backend_s", "run_s", "outside_s")


def report_of(run):
    """The program's report over the run's set-up, or None.  A run that
    carries ``setup_report`` (a recorded one, in a test) is read as it is."""
    if "setup_report" in run:
        return run["setup_report"]
    from paddle_tpu import profiler

    if not hasattr(profiler, "setup_report"):
        return None
    return profiler.setup_report(run["t_open"] - run["setup_s"], run["t_open"])


def _compile_seconds(c):
    return c["trace_s"] + c["lower_s"] + c["backend_s"]


def readings(run):
    """{reading: seconds} for the six of ``READINGS``, or None."""
    if "t_open" not in run or "setup_s" not in run:
        return None
    rep = report_of(run)
    if not rep:
        return None
    t1 = run["t_open"]
    t0 = t1 - run["setup_s"]
    stages = {s["id"]: s for s in rep["stages"]}
    compiles = [c for c in rep["compiles"] if t0 <= c["t0"] < t1]

    def own(s):
        """A stage's seconds inside the interval, less what jax compiled there."""
        return min(s["t0"] + s["seconds"], t1) - max(s["t0"], t0) - s["compile_s"]

    def under_build(s):
        p = stages.get(s["parent"])
        return p is not None and (p["name"] in BUILD or under_build(p))

    builds = [s for s in stages.values() if s["name"] in BUILD and not under_build(s)]
    acquired = [s["t0"] for s in stages.values() if s["name"] == "program.acquire"]
    run_s = 0.0
    if acquired:
        first = max(min(acquired), t0)
        run_s = ((t1 - first) - sum(_compile_seconds(c) for c in compiles if c["t0"] >= first)
                 - sum(own(s) for s in builds if s["t0"] >= first))
    out = {"import_s": sum(own(s) for s in stages.values() if s["name"] == "setup.import"),
           "build_s": sum(own(s) for s in builds),
           "trace_s": sum(c["trace_s"] + c["lower_s"] for c in compiles),
           "backend_s": sum(c["backend_s"] for c in compiles),
           "run_s": run_s}
    out["outside_s"] = run["setup_s"] - sum(out.values())
    return out


def reading(run, name):
    got = readings(run)
    return None if got is None else got[name]


def dearest(run, n=5):
    """The ``n`` programs that cost most to acquire, by name, and the rest's sum:
    what ``setup_backend_s`` prints beside its number."""
    rep = report_of(run)
    keep = ("program", "kind", "k", "trace_s", "lower_s", "backend_s", "cache_hit")
    other = rep["other"]
    return {"programs": [{k: p.get(k) for k in keep} for p in rep["programs"][:n]],
            "other": {k: other[k] for k in ("count", "trace_s", "lower_s", "backend_s",
                                            "cache_hits")}}
