"""From a jax.profiler trace to numbers. ``load`` turns the newest
``.xplane.pb`` under a directory into plain lists, and every reduction below
works on those lists, so a small recorded trace kept as JSON checks them
(tests/benchmark). Times are nanoseconds on the profiler's clock, which the
host spans (``jax.profiler.TraceAnnotation``) and the device lines share."""
import glob
import os
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Trace:
    # {device plane: {line: [(name, start_ns, dur_ns), ...]}}
    devices: dict = field(default_factory=dict)
    # host spans of the benchmark's own annotations: [(name, start_ns, dur_ns)]
    host: list = field(default_factory=list)

    def to_dict(self):
        return {"devices": self.devices, "host": self.host}

    @classmethod
    def from_dict(cls, d):
        devices = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
                   for p, lines in d["devices"].items()}
        return cls(devices=devices, host=[tuple(e) for e in d["host"]])


def start(log_dir):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the interpreter's frames are not wanted
    opts.host_tracer_level = 2       # TraceAnnotation spans are
    opts.enable_hlo_proto = False
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop():
    import jax

    jax.profiler.stop_trace()


def load(log_dir, host_prefix="bench.") -> Trace:
    """The newest trace under ``log_dir``. Host events are kept only where
    their name starts with ``host_prefix``: the benchmark's own spans."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(short_name(e.name), int(e.start_ns),
                                         int(e.duration_ns)) for e in line.events]
            tr.devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        tr.host.append((e.name, int(e.start_ns), int(e.duration_ns)))
    tr.host.sort(key=lambda e: e[1])
    return tr


def short_name(name):
    """An operation's event carries its whole HLO text, ``%fusion.12 = (...)
    fusion(...)``: keep ``fusion.12``. A custom call (a Pallas kernel) is
    named by the transform it was traced under (``checkpoint.5``, ``jvp__.3``),
    so it is marked: ``custom-call:checkpoint.5``. A module's ``jit_step(123)``
    stays."""
    short = name.split(" = ", 1)[0].lstrip("%")
    if " custom-call(" in name and not short.startswith("custom-call"):
        return "custom-call:" + short
    return short


def is_custom_call(name):
    return name.startswith("custom-call")


# ------------------------------------------------------------- reductions
def merge(intervals):
    """Union of (start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _ops(trace, plane):
    return trace.devices[plane].get(OPS_LINE, [])


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def window_ns(trace):
    """The traced window: the ``bench.window`` host span where there is one,
    else from the first device operation to the last."""
    for name, s, d in trace.host:
        if name == "bench.window":
            return s, s + d
    starts = [s for p in trace.devices for _, s, _ in _ops(trace, p)]
    ends = [s + d for p in trace.devices for _, s, d in _ops(trace, p)]
    if not starts:
        raise ValueError("no device operation in the trace")
    return min(starts), max(ends)


def busy_by_device(trace, t0=None, t1=None):
    """{plane: seconds in which some operation ran on it} inside [t0, t1]."""
    if t0 is None:
        t0, t1 = window_ns(trace)
    out = {}
    for plane in trace.devices:
        iv = _clip([(s, s + d) for _, s, d in _ops(trace, plane)], t0, t1)
        out[plane] = sum(e - s for s, e in merge(iv)) / 1e9
    return out


def busy_and_window(trace):
    """(busy seconds averaged over the devices, window seconds)."""
    t0, t1 = window_ns(trace)
    busy = busy_by_device(trace, t0, t1)
    if not busy:
        raise ValueError("no device plane in the trace")
    return sum(busy.values()) / len(busy), (t1 - t0) / 1e9


def module_durations(trace, prefixes, plane=None):
    """Seconds of each ``XLA Modules`` event on one device whose name starts
    with one of ``prefixes`` (a jitted function ``f`` runs as ``jit_f``),
    inside the window."""
    plane = plane or sorted(trace.devices)[0]
    t0, t1 = window_ns(trace)
    return [d / 1e9 for name, s, d in trace.devices[plane].get(MODULES_LINE, [])
            if name.startswith(tuple(prefixes)) and s >= t0 and s + d <= t1]


def op_seconds(trace, match, plane=None):
    """Summed seconds, inside the window, of the operations on one device
    for which ``match(name)`` holds."""
    plane = plane or sorted(trace.devices)[0]
    t0, t1 = window_ns(trace)
    return sum(e - s for name, s0, d in _ops(trace, plane) if match(name)
               for s, e in _clip([(s0, s0 + d)], t0, t1)) / 1e9


def top_ops(trace, n=10, plane=None):
    """[[name, seconds], ...]: the operations that took most device time.
    Instances of one HLO name (``fusion.12``) are summed; a loop or a branch
    is left out, because the operations inside it are events of their own."""
    plane = plane or sorted(trace.devices)[0]
    t0, t1 = window_ns(trace)
    tot = {}
    for name, s, d in _ops(trace, plane):
        if name.startswith(CONTAINERS):
            continue
        for a, b in _clip([(s, s + d)], t0, t1):
            tot[name] = tot.get(name, 0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(trace, n=10, plane=None):
    """[[what the host was doing, seconds], ...]: the device's idle time
    inside the window, summed by the innermost benchmark span that covers
    the middle of each gap (``(no span)`` where none does)."""
    plane = plane or sorted(trace.devices)[0]
    t0, t1 = window_ns(trace)
    busy = merge(_clip([(s, s + d) for _, s, d in _ops(trace, plane)], t0, t1))
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    spans = [(s, s + d, name) for name, s, d in trace.host if name != "bench.window"]
    tot = {}
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(b - a, name) for a, b, name in spans if a <= mid < b]
        what = min(cover)[1] if cover else "(no span)"
        tot[what] = tot.get(what, 0) + (e - s)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def breakdown(trace):
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
