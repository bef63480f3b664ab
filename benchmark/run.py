"""The benchmark's one command:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run. It holds the cell's chips, builds the cell's
configuration with weights made from ``--seed``, warms the cell's own shapes
(set-up), measures for ``--seconds``, checks what the timed path produced
against the plain reference outside the window, prints each number compared
beside its limit and, as the last line, one JSON object.

It knows no cell, model or metric by name: BENCHMARK.json names the cell's
configuration and traffic files; the configuration names its driver
(benchmark/drivers/<path>.py), family and reference; the traffic file names
its generator; each metric is a module under benchmark/end_to_end or
benchmark/layer_metrics. ``--control 1`` (never passed by a check) puts the
lower precision of the configuration's ``control`` in the program's place:
``correct`` must then come out false."""
import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from benchmark.harness import loader, peaks, report, xplane  # noqa: E402

NO_RESULT = 3            # exit code where the run cannot be made at all


class RunContext:
    """What a driver needs of the run: the clock, the compile meter, whether
    and where to trace, the checks it adds to."""

    def __init__(self, args, meter, out_dir):
        self.clock = time.perf_counter
        self.meter = meter
        # a traced run profiles the window's last seconds, so that stopping
        # the profiler (seconds of writing) falls after the window
        self.trace_from = max(0.0, float(args.seconds) - 5.0) if args.trace else None
        self.control = bool(args.control)
        self.checks = report.Checks()
        self.trace_dir = os.path.join(out_dir, "trace")
        self.memory_peak_bytes = None
        self.traced = False
        self._window_span = None

    def trace_tick(self, rel):
        """Called through the window with the seconds since it opened."""
        if self.trace_from is None or self.traced or rel < self.trace_from:
            return
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        xplane.start(self.trace_dir)
        self.traced = True
        self._window_span = jax.profiler.TraceAnnotation("bench.window")
        self._window_span.__enter__()

    def trace_close(self):
        """Called as the window closes."""
        if self.traced and self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None
            xplane.stop()

    def read_memory_peak(self, chips=1):
        """Peak bytes on the fullest chip, read while only the program's
        state has been on the device."""
        import jax

        stats = [d.memory_stats() for d in jax.devices()[:chips]]
        if all(stats):           # the CPU of the tests reports none
            self.memory_peak_bytes = max(int(s["peak_bytes_in_use"]) for s in stats)
        return self.memory_peak_bytes


def find_device(chips):
    """The device line of the result, or exit: no accelerator, too few chips
    or a kind without published peaks is not a run."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"benchmark needs a TPU; jax found {d.platform!r}")
    if len(devs) < chips:
        sys.exit(f"the cell asks for {chips} chips; jax found {len(devs)}")
    peaks.peaks_for(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def measure(cell, args, device, meter, out_dir):
    """Drive one run of ``cell`` on the devices JAX holds -> the result line.
    Split from ``main`` so that a test can drive a run where there is no
    chip."""
    ctx = RunContext(args, meter, out_dir)
    driver = cell.module("drivers", cell.config["path"])
    run = driver.run(cell, args.seed, float(args.seconds), ctx)
    run.update(cell=cell, config=cell.config, traffic=cell.traffic,
               seconds=float(args.seconds), setup_s=run["t_open"] - T_PROCESS,
               peaks=peaks.PEAKS.get(device["kind"]), chips=cell.chips,
               memory_peak_bytes=ctx.memory_peak_bytes, trace=None)
    ctx.checks.add("compiles_in_window", run["compiles_in_window"], 0)
    if ctx.traced:
        run["trace"] = xplane.load(ctx.trace_dir)
        busy, window = xplane.busy_and_window(run["trace"])
        device = dict(device, busy_s=busy, window_s=window)

    kind, entries = (("layer_metrics", cell.per_layer) if args.trace
                     else ("end_to_end", cell.end_to_end))
    metrics, units = {}, {}
    for m in ([] if run.get("no_window") else entries):
        value = cell.module(kind, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]], units[m["name"]] = float(value), m["unit"]
    report.note(setup_s=run["setup_s"], window_s=run["window_s"], attempted=run["attempted"],
                failed=run["failed"], memory_peak_bytes=ctx.memory_peak_bytes)
    ctx.checks.print()
    device = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    return report.result_line(
        correct=ctx.checks.correct, attempted=run["attempted"], failed=run["failed"],
        metrics=metrics, units=units, device=device,
        breakdown=xplane.breakdown(run["trace"]) if run["trace"] else None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = loader.load_cell(args.workload)
    device = find_device(cell.chips)

    import jax

    from paddle_tpu.jit import use_compile_cache

    from benchmark.harness.compile_meter import CompileMeter

    cache_dir = use_compile_cache()
    # every program, however quick to compile, is read back by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    meter = CompileMeter()
    out_dir = os.path.join(loader.ROOT, "benchmark_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    report.note(workload=args.workload, seed=args.seed, seconds=args.seconds,
                trace=args.trace, control=args.control, compile_cache=cache_dir)
    line = measure(cell, args, device, meter, out_dir)
    report.note(programs_compiled=meter.programs, cache_hits=meter.cache_hits,
                compile_seconds=meter.seconds)
    print(line, flush=True)


if __name__ == "__main__":
    main()
