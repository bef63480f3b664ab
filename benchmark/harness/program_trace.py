"""The program's own names in a run's trace: the host spans the program opens
(``frontend.*``, ``engine.*``, ``train_step.*``, each a ``RecordEvent`` with
its stats) and the device's operations with the scope path their events carry,
read from the same ``.xplane.pb`` that ``xplane.load`` reads (which keeps the
``bench.*`` spans and short names only). Plain lists, like ``xplane.Trace``,
so the reductions below are checked on a small recorded trace kept as JSON
(tests/benchmark/recorded_program_trace.json).

What a raw device event carries on a TPU v5e (PERF.md section 6, PR 24): its
name is the instruction's HLO text, ``%flash_fwd.1 = ... custom-call(...)``,
so a kernel shows the name its ``pallas_call`` was given; its stats are its
offset and duration and nothing else, so no event carries the
``jax.named_scope`` path of its operation while ``xplane.start`` keeps
``enable_hlo_proto`` off. ``path`` is then empty and ``scope_seconds`` finds
nothing; the reductions over paths are here, checked, for the day it is on."""
import glob
import os
import re
from dataclasses import dataclass, field

from benchmark.harness import flash_cost, loader, report, xplane

PREFIXES = ("frontend.", "engine.", "train_step.")
# the engine's programs as module events, and the ``kind`` their launch carries
KIND_OF = {"jit_step": "step", "jit_mega": "mega", "jit_mixed": "mixed",
           "jit_spec_verify": "spec"}
ENGINE_MODULES = tuple(KIND_OF)
PATH_STAT = "tf_op"          # xprof's name for an operation's op_name path
CALLER = "caller"            # idle time under no span of the program


@dataclass
class ProgramTrace:
    window: tuple                                   # (t0_ns, t1_ns)
    host: list = field(default_factory=list)        # [(name, start_ns, dur_ns, stats)]
    modules: list = field(default_factory=list)     # [(name, start_ns, dur_ns)]
    ops: list = field(default_factory=list)         # [(short, path, start_ns, dur_ns)]

    def to_dict(self):
        return {"window": list(self.window), "host": self.host,
                "modules": self.modules, "ops": self.ops}

    @classmethod
    def from_dict(cls, d):
        return cls(window=tuple(d["window"]),
                   host=[(n, s, dur, dict(st)) for n, s, dur, st in d["host"]],
                   modules=[tuple(e) for e in d["modules"]],
                   ops=[tuple(e) for e in d["ops"]])


def trace_dir(run):
    """Where ``benchmark.run`` had the profiler write this run's trace."""
    return os.path.join(loader.ROOT, "benchmark_out", run["cell"].name, "trace")


def of(run):
    """The run's program trace, loaded once and kept on ``run``; None where
    the run was not traced or its trace file cannot be found."""
    if run.get("trace") is None or run.get("cell") is None:
        return None
    if "program_trace" not in run:
        run["program_trace"] = load(trace_dir(run))
    return run["program_trace"]


def load(log_dir):
    """The newest trace under ``log_dir`` -> ProgramTrace (first device), or
    None where there is none."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    from jax.profiler import ProfileData

    host, window, devices = [], None, {}
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith(xplane.DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = (
                [(xplane.short_name(e.name), int(e.start_ns), int(e.duration_ns))
                 for e in lines[xplane.MODULES_LINE].events]
                if xplane.MODULES_LINE in lines else [],
                [(xplane.short_name(e.name), str(dict(e.stats).get(PATH_STAT, "")),
                  int(e.start_ns), int(e.duration_ns))
                 for e in lines[xplane.OPS_LINE].events]
                if xplane.OPS_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = (int(e.start_ns), int(e.start_ns + e.duration_ns))
                    elif e.name.startswith(PREFIXES):
                        host.append((e.name, int(e.start_ns), int(e.duration_ns),
                                     dict(e.stats)))
    if not devices:
        return None
    modules, ops = devices[sorted(devices)[0]]
    if window is None:
        if not ops:
            return None
        window = (min(s for _, _, s, _ in ops), max(s + d for _, _, s, d in ops))
    host.sort(key=lambda e: e[1])
    return ProgramTrace(window=window, host=host, modules=modules, ops=ops)


# ------------------------------------------------------------- reductions
def modules_in(trace, prefixes):
    """[(start_ns, end_ns)] of the module events named by ``prefixes`` (a
    jitted function ``f`` runs as ``jit_f``) that lie inside the window."""
    t0, t1 = trace.window
    return sorted((s, s + d) for name, s, d in trace.modules
                  if name.startswith(tuple(prefixes)) and s >= t0 and s + d <= t1)


def _inside(start, intervals):
    return any(a <= start < b for a, b in intervals)


def has_scope(path, scope):
    """Whether ``scope`` (``a`` or ``a/b``) is an element of the op_name
    path, inside whatever wrapper jax wrote around it (``jvp(a)``)."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", path) is not None


def direction(path):
    """``forward`` / ``backward`` / None from the wrappers jax writes into an
    op_name path. A recomputation under ``checkpoint`` runs in the backward
    pass and counts there."""
    if "transpose(" in path or "rematted_computation" in path or "checkpoint" in path:
        return "backward"
    return "forward" if "jvp(" in path else None


def _leaf_ops(trace, modules):
    """Operations inside the named module events; a loop, a branch or a call
    is left out, as in ``xplane.top_ops``: what runs inside it has events of
    its own."""
    inside = modules_in(trace, modules)
    return [op for op in trace.ops if not op[0].startswith(xplane.CONTAINERS)
            and _inside(op[2], inside)]


def scope_seconds(trace, modules, scopes):
    """Seconds of the operations inside the named module events whose path
    holds one of ``scopes``; ``scopes`` None: of those under no path at all."""
    ops = _leaf_ops(trace, modules)
    if scopes is None:
        return sum(d for _, path, _, d in ops if not path) / 1e9
    return sum(d for _, path, _, d in ops
               if any(has_scope(path, s) for s in scopes)) / 1e9


def iterations(trace, modules, megastep_k):
    """Scan iterations the named module events ran: the ``k`` of the
    ``engine.launch`` spans of their kinds where the trace has them, else
    launches x ``megastep_k``."""
    launches = len(modules_in(trace, modules))
    kinds = {KIND_OF[m] for m in modules}
    t0, t1 = trace.window
    ks = [st["k"] for name, s, d, st in trace.host
          if name == "engine.launch" and st.get("kind") in kinds and s >= t0 and s + d <= t1]
    return int(sum(ks)) if len(ks) == launches and ks else launches * megastep_k


def kernel_name(short):
    """``custom-call:flash_fwd.3`` -> ``flash_fwd``."""
    return re.sub(r"\.\d+$", "", short.split(":", 1)[-1])


def kernel_seconds(trace, names, modules):
    """One dict for each of the named module events, {kernel: [events,
    seconds]} over the kernels of ``names`` that ran inside it."""
    hits = [(name, s, d) for name, s, d in
            ((kernel_name(short), s, d) for short, _, s, d in trace.ops) if name in names]
    out = []
    for a, b in modules_in(trace, modules):
        per = {}
        for name, s, d in hits:
            if a <= s < b:
                got = per.setdefault(name, [0, 0.0])
                got[0] += 1
                got[1] += d / 1e9
        out.append(per)
    return out


def gaps_by_span(trace, modules):
    """(launches, {span: idle seconds}): the device's idle time inside the
    window (from its opening to the first module event, between module
    events, and from the last to its close), each instant of it put down to
    the innermost program span that covers it, ``caller`` under none.
    ``launches`` counts the module events named by ``modules``."""
    t0, t1 = trace.window
    busy = xplane.merge([(max(s, t0), min(s + d, t1)) for _, s, d in trace.modules
                         if s + d > t0 and s < t1])
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    spans = [(s, s + d, name) for name, s, d, _ in trace.host]
    out = {}
    for a, b in gaps:
        over = [sp for sp in spans if sp[0] < b and sp[1] > a]
        cuts = sorted({a, b} | {t for s, e, _ in over for t in (s, e) if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            cover = [(e - s, name) for s, e, name in over if s <= mid < e]
            what = min(cover)[1] if cover else CALLER
            out[what] = out.get(what, 0.0) + (hi - lo) / 1e9
    return len(modules_in(trace, modules)), out


# --------------------------------------------------- what the metrics read
def launch_gap_ms(run, spans=None):
    """Device idle time a launch of the engine's programs, in ms: all of it
    (``spans`` None), or the part under the named spans (``frontend.`` names
    every span of the frontend). None where the trace has no launch or, for a
    part, no span of the program."""
    trace = of(run)
    if trace is None:
        return None
    launches, by_span = gaps_by_span(trace, ENGINE_MODULES)
    if not launches or (spans is not None and not trace.host):
        return None
    idle = sum(v for name, v in by_span.items()
               if spans is None or name.startswith(tuple(spans)))
    return 1000.0 * idle / launches


def step_host_ms(run):
    """Median duration of the ``train_step.call`` spans inside the window."""
    trace = of(run)
    if trace is None:
        return None
    t0, t1 = trace.window
    durs = [d / 1e6 for name, s, d, _ in trace.host
            if name == "train_step.call" and s >= t0 and s + d <= t1]
    return report.median(durs) if durs else None


def flash_per_step(run):
    """(seconds, forward kernel runs, backward passes) of the flash kernels
    in the median ``jit_step`` module event; None where no kernel of those
    names ran (a program whose kernels carry no names)."""
    trace = of(run)
    if trace is None:
        return None
    steps = [per for per in kernel_seconds(trace, flash_cost.KERNELS, ("jit_step",)) if per]
    if not steps:
        return None
    med = sorted(steps, key=lambda per: sum(v[1] for v in per.values()))[len(steps) // 2]
    return (sum(v[1] for v in med.values()), med.get("flash_fwd", [0])[0],
            med.get("flash_bwd_dq", [0])[0])
