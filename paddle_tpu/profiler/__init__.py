"""Profiler (parity: python/paddle/profiler — Profiler ctx mgr with
CLOSED→READY→RECORD scheduler profiler.py:79,346, chrome-trace export,
summary tables profiler_statistic.py, step timer/ips timer.py).

TPU-native: device tracing is jax.profiler (XPlane → TensorBoard/Perfetto,
replacing the reference's CUPTI tracer); host spans use
jax.profiler.TraceAnnotation (the RecordEvent analog); the step-timer /
throughput surface is reimplemented natively.

What the program names, so that a trace taken by ``Profiler`` or by
``jax.profiler.start_trace`` around a running engine or train loop reads in
the program's words (the table with each name's reader is PERF.md section 3):

Host spans, every one a ``RecordEvent`` on the profiler's clock:
``frontend.step`` > ``frontend.dispatch``, ``engine.step``, ``frontend.deliver``;
``engine.step`` > ``engine.admit``, ``engine.schedule``, ``engine.launch``
(stats ``kind`` step|mega|mixed|spec, ``k``, ``launch``, ``t_mono``,
``passes`` (how often an iteration runs the model's layers: 1 but for a
looped model), ``arrays_up`` and ``bytes_up``, the control arrays the launch
sent to the device with its dispatch and their bytes (ONE packed block:
inference/launch_block.py), and on a mixed launch ``prefill_rows``, the rows
it feeds prompt chunks),
``engine.wait`` (stats ``reads``: the blocking reads it made of copies started
at the dispatch, 1, and one more where a scheduled row asked for
log-probabilities), ``engine.harvest`` (stats: what the model's trunk counted
in the launch, each from the op that did it: ``paged_counts``'s six for a
dense paged cache (ops/paged_attention.py: ``attn_positions_live`` /
``attn_positions_read`` / ``attn_rows_kernel`` / ``attn_chunks_kernel`` (the
one-token and the chunk rows an iteration that attended in the ``paged_decode``
and the ``paged_chunk`` kernel; 0 where the XLA pass ran), ``kv_write_tokens`` /
``kv_write_blocks``); ``moe_tokens`` / ``moe_local_picks`` (``_moe_ffn``) and
``expert_rows_grouped`` (ops/held_experts.py: the picks that went through the
grouped product; 0 where the tile loop ran) for expert layers, and where the
trunk seeds them ``experts_touched`` / ``expert_tiles`` (held experts with a
row, and the row tiles in use: tiles an expert, 1 where the layout of the
sorted rows fits the routing) / ``expert_tile_rows`` / ``expert_tile_rows_live``; ``loop_tokens``
/ ``loop_token_passes`` for a looped trunk: tokens fed, and tokens x passes
run; ``dsa_queries`` / ``dsa_positions_scored`` / ``dsa_positions_selected``
(ops/sparse_index.py) / ``dsa_positions_read`` (``selection_counts``) for
learned sparse attention: ONE layer's, over the live queries whose context
exceeds the model's ``index_topk``, beside ``attn_positions_live``;
``latent_rows_kernel`` / ``latent_chunks_kernel`` (``latent_counts``) for a
latent cache: the one-token and the chunk rows an iteration whose blocked pass
ran in the ``latent_rows`` kernel; 0 where the XLA loops ran; for a model of
several KINDS of cache layer (inference/serving_model.py) ONE layer of each
kind's ``attn_positions_live.<kind>`` / ``attn_positions_read.<kind>`` /
``attn_chunks_kernel.<kind>`` and
``window_positions_spared`` (the live context behind the first key a fed row's
first query attends), and the engine's totals as the span STARTS,
``window_blocks_released`` (monotone) and ``window_blocks_held``);
``train_step.call`` (stats ``step``, ``steps``).

Set-up spans, every one a ``SetupSpan``: a ``RecordEvent`` that also leaves a
row ``{id, name, parent, attrs, t0, seconds}`` on ``time.perf_counter`` in the
process-wide, bounded ``SETUP`` ledger (``parent``: the set-up span open when
it opened; the steady-state spans above write no rows):
``setup.import`` (``paddle_tpu/__init__.py`` first line to last, written after
the fact; attr ``jax_loaded``: jax was imported before it);
``model.init`` (the served causal-LM constructors; attrs ``family``,
``dtype``, ``parameters``);
``engine.init`` > ``engine.init.weights`` (``model.serving_weights`` and the
rope table: cast, stack, place; attr ``bytes``), ``engine.init.pool`` (the
cache arrays; attr ``bytes``), ``engine.init.programs`` (``_build_*`` and the
``_PROGRAM_CACHE`` lookup; attr ``shared``: another engine's programs were
taken); ``frontend.init``; ``train_step.init`` (optimizer state and masters;
attr ``bytes``);
``program.acquire`` (written after the fact, on the launch or train step that
found its jitted program's cache grown: trace, lowering, compile or cache read
and the first execution's enqueue; attrs ``program`` (the jitted function's
name), ``kind`` and ``k`` as ``engine.launch`` has them (``kind`` ``train`` for
the train step), and the compile ledger's row of it: ``trace_s``, ``lower_s``,
``backend_s``, ``cache_hit``, ``cache_read_s``).

The compile ledger (``SETUP.compiles``), fed by ONE pair of ``jax.monitoring``
listeners that fire where jax traces, lowers or compiles and nowhere else: a
row a program handed to the backend, ``{fun_name, trace_s, lower_s,
backend_s, cache_hit, cache_read_s, t0, acquired}`` (``backend_s``: XLA
compiling, or the persistent cache read back; ``acquired``: a
``program.acquire`` claimed it; the rest, jax's own small programs and the
eager helpers of a model's build, are what ``setup_report()`` sums under
``other``).  ``setup_report()`` is what an operator reads.

Device scopes (``jax.named_scope``: metadata in the compiled program, nothing
at run time), one vocabulary for every model family:
``embed``, ``attn_proj``, ``paged_attention`` > ``rope`` ``kv_write`` (on the
chip the write is the ``paged_write`` kernel inside it) and,
under ``while/body/`` once for each loop around them (row tiles or chunk
rows, then context blocks), ``kv_gather`` ``scores`` ``values`` (on the
chip a row is in none of the three: a one-token row attends inside the
``paged_decode`` kernel, a chunk row inside ``paged_chunk``); ``attn_out``,
``mlp``, ``norm``, ``head``, ``sample``, ``scan_carry`` (the serving
programs); ``post_norm`` (a sandwich block's norm on a sublayer's output);
``loop_pass`` > ``while/body/`` the layers' scopes, ``norm``, ``exit_gate``
(one pass of a looped trunk, itself the body of the loop over the passes);
``latent_proj``, ``latent_attention`` > ``kv_write`` and the three under
``while/body/`` (a latent cache; on the chip ``rows_kernel``, the
``latent_rows`` kernel, in place of the three, beside ``select_gather``
where a selection is given), ``router``, ``experts`` (on the chip three
``expert_gmm`` kernels; elsewhere the tile loop, ``experts/while/body/``),
``shared_expert`` (expert layers; ``shared_experts`` where a parallel block
averages several as one wide unit); ``indexer`` > ``index_proj``, ``index_write``,
``while/body/`` {``index_gather``, ``index_scores``}, ``index_topk`` (the
selector of learned sparse attention, ops/sparse_index.py);
``attention`` >
``flash_attention``, ``loss``, ``optimizer``, ``grad_unscale`` (the train
step, which shares ``embed`` ``attn_proj`` ``attn_out`` ``mlp`` ``norm``
``head``).

Kernels (``pallas_call(name=)``, the name of the custom call's device event):
``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, ``rms_norm``,
``rms_norm_residual``, ``fused_rope`` (q and k apart under ``attention``),
``swiglu_fwd``, ``swiglu_bwd`` (under ``mlp``; the three in every train step of
the dense trunk on one chip: 12, 4 and 2 a step at depth 2 under recompute),
``int8_matmul``, ``paged_decode``, ``paged_write``, ``paged_chunk`` (one a cache
layer under ``paged_attention``, on the chip, where a row may feed more than
one token), ``expert_gmm`` (three a
layer under ``experts``, on the chip: gate, up, down), ``latent_rows`` (one a
layer under ``latent_attention/rows_kernel``, on the chip).
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import re
import threading
import time
from collections import defaultdict, deque
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "make_scheduler", "export_chrome_tracing",
    "RecordEvent", "benchmark", "SummaryView",
    "SetupSpan", "SetupLedger", "SETUP", "setup_report",
]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


def make_scheduler(closed: int, ready: int, record: int, repeat: int = 0, skip_first: int = 0):
    """parity: profiler.make_scheduler — step-indexed state machine."""
    cycle = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(dir_name, f"{worker_name or 'worker'}_{int(time.time())}.json")
        prof._export_host_events(path)

    return handler


class RecordEvent:
    """Host span (parity: paddle.profiler.RecordEvent / C++ RecordEvent).
    ``attrs`` become the stats of the span's event in the profiler's trace;
    the ``perf_counter`` record for ``Profiler.summary()`` is taken only
    while a ``Profiler`` collects."""

    _active_sink = None

    def __init__(self, name: str, event_type=None, **attrs):
        self.name = name
        self._attrs = attrs
        self._jax_ann = None
        self._t0 = None

    def begin(self):
        if RecordEvent._active_sink is not None:
            self._t0 = time.perf_counter()
        self._jax_ann = jax.profiler.TraceAnnotation(self.name, **self._attrs)
        self._jax_ann.__enter__()

    def end(self):
        if self._jax_ann is not None:
            self._jax_ann.__exit__(None, None, None)
            self._jax_ann = None
        sink = RecordEvent._active_sink
        if sink is not None and self._t0 is not None:
            sink.append((self.name, self._t0, time.perf_counter() - self._t0))
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


# jax.monitoring's names for what a jitted call does before it can run
# (jax/_src/dispatch.py, compiler.py); each duration carries ``fun_name``
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_MODULE = re.compile(r"^\w+\((.*)\)$")            # "jit(step)" -> "step"
_COMPILE_SECONDS = ("trace_s", "lower_s", "backend_s")


def _compile_seconds(row) -> float:
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


def _compile_row(fun_name, t0, trace_s=0.0, lower_s=0.0) -> dict:
    return {"fun_name": fun_name, "trace_s": trace_s, "lower_s": lower_s, "backend_s": 0.0,
            "cache_hit": False, "cache_read_s": 0.0, "t0": t0, "acquired": False}


class _ThreadState(threading.local):
    """A thread's open set-up spans, the traces it has seen since its last
    lowering, and the program it has lowered and not yet compiled."""

    def __init__(self):
        self.spans, self.traces, self.flight = [], {}, None


class SetupLedger:
    """Where a process's seconds go before its first launch: the rows of the
    set-up spans and the compile ledger (the module's docstring has the
    names).  Both lists are bounded: overflow drops the oldest and counts
    it, as ``FlightRecorder`` does."""

    def __init__(self, capacity: int = 512, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.rows: deque = deque(maxlen=int(capacity))
        self.compiles: deque = deque(maxlen=4 * int(capacity))
        self.dropped_rows = 0
        self.dropped_compiles = 0
        self._ids = itertools.count(1)
        self._local = _ThreadState()

    # ------------------------------------------------------------ span rows
    def innermost(self) -> Optional["SetupSpan"]:
        """The set-up span this thread has open, if any."""
        spans = self._local.spans
        return spans[-1] if spans else None

    def _append(self, span_id, parent, name, attrs, t0, seconds) -> dict:
        row = {"id": span_id, "name": name, "parent": parent, "attrs": attrs,
               "t0": t0, "seconds": seconds}
        if len(self.rows) == self.rows.maxlen:
            self.dropped_rows += 1
        self.rows.append(row)
        return row

    def record(self, name: str, t0: float, seconds: float, **attrs) -> dict:
        """One row, after the fact, under the span this thread has open now."""
        inner = self.innermost()
        return self._append(next(self._ids), inner.id if inner is not None else None,
                            name, attrs, t0, seconds)

    def acquired(self, program: str, seconds: float, since=None, **attrs) -> dict:
        """``program.acquire``, after the fact: the call of the jitted function
        ``program`` that has just returned took ``seconds`` and had to trace,
        lower and compile or read it.  The compile ledger's newest unclaimed
        row of that name is its own, and its numbers ride the row.  ``since``:
        this ledger's clock when the call began, where the caller read it: a
        row from before it is another program's of the same name (an engine's
        ``step`` left unclaimed beside a train step's), and is not claimed."""
        end = self.clock()
        for c in reversed(self.compiles):
            if (c["fun_name"] == program and not c["acquired"]
                    and (since is None or c["t0"] >= since - 0.01)):
                c["acquired"] = True
                attrs.update({k: c[k] for k in
                              _COMPILE_SECONDS + ("cache_hit", "cache_read_s")})
                break
        return self.record("program.acquire", end - seconds, seconds,
                           program=program, **attrs)

    # -------------------------------------------------------- compile ledger
    def _on_duration(self, event, secs, fun_name=None, **_):
        st = self._local
        if event == _TRACE:
            # every jitted function traced on the way fires one; the program's
            # own is the one its lowering then names
            if len(st.traces) > 256:
                st.traces.clear()
            st.traces[fun_name] = secs
        elif event == _LOWER:
            name = _MODULE.sub(r"\1", fun_name or "")
            trace_s = st.traces.get(name, 0.0)
            st.traces.clear()
            st.flight = _compile_row(name, self.clock() - secs - trace_s, trace_s, secs)
        elif event == _CACHE_READ:
            if st.flight is not None:
                st.flight["cache_read_s"] = secs
        elif event == _BACKEND:
            name = _MODULE.sub(r"\1", fun_name or "")
            row, st.flight = st.flight, None
            if row is None or row["fun_name"] != name:    # compiled from a lowering kept
                row = _compile_row(name, self.clock() - secs)
            row["backend_s"] = secs
            if len(self.compiles) == self.compiles.maxlen:
                self.dropped_compiles += 1
            self.compiles.append(row)

    def _on_event(self, event, **_):
        if event == _CACHE_HIT and self._local.flight is not None:
            self._local.flight["cache_hit"] = True

    # --------------------------------------------------------------- report
    def report(self, since: Optional[float] = None, until: Optional[float] = None) -> dict:
        """What ``setup_report`` returns, over ``[since, until]`` on the
        ledger's clock (by default from the first row to the end of the last)."""
        rows, compiles = list(self.rows), list(self.compiles)
        starts = [r["t0"] for r in rows] + [c["t0"] for c in compiles]
        ends = ([r["t0"] + r["seconds"] for r in rows]
                + [c["t0"] + _compile_seconds(c) for c in compiles])
        if since is None:
            since = min(starts, default=self.clock())
        if until is None:
            until = max(ends + [since])
        rows = sorted((r for r in rows if r["t0"] < until and r["t0"] + r["seconds"] > since),
                      key=lambda r: (r["t0"], r["id"]))
        compiles = [c for c in compiles if since <= c["t0"] < until]
        kept = {r["id"] for r in rows}
        stages, named = [], 0.0
        for r in rows:
            end = r["t0"] + r["seconds"]
            children = sum(c["seconds"] for c in rows if c["parent"] == r["id"])
            stage = dict(r, self_s=r["seconds"] - children,
                         compile_s=sum(_compile_seconds(c) for c in compiles
                                       if r["t0"] <= c["t0"] < end))
            stages.append(stage)
            if r["parent"] not in kept:           # top level: no two overlap on a thread
                named += min(end, until) - max(r["t0"], since)
        programs = sorted((dict(r["attrs"], t0=r["t0"], seconds=r["seconds"])
                           for r in rows if r["name"] == "program.acquire"),
                          key=lambda p: -sum(p.get(k, 0.0) for k in _COMPILE_SECONDS))
        rest = [c for c in compiles if not c["acquired"]]
        by_name = defaultdict(lambda: [0, 0.0])
        for c in rest:
            by_name[c["fun_name"]][0] += 1
            by_name[c["fun_name"]][1] += _compile_seconds(c)
        other = {"count": len(rest), "cache_hits": sum(c["cache_hit"] for c in rest),
                 "by_name": sorted(([n, k, s] for n, (k, s) in by_name.items()),
                                   key=lambda e: -e[2])[:5]}
        other.update({k: sum(c[k] for c in rest) for k in _COMPILE_SECONDS})
        out = {"since": since, "until": until, "seconds": until - since,
               "stages": stages, "programs": programs, "other": other,
               "compiles": compiles, "unnamed_s": (until - since) - named,
               "dropped": {"rows": self.dropped_rows, "compiles": self.dropped_compiles}}
        out["text"] = _setup_table(out)
        return out


def _setup_table(rep: dict) -> str:
    """About ten lines: the stages by name, the dearest programs, the rest."""
    depth, agg = {}, {}
    for s in rep["stages"]:
        depth[s["id"]] = depth.get(s["parent"], -1) + 1
        a = agg.setdefault(s["name"], [depth[s["id"]], 0, 0.0, 0.0])
        a[1] += 1
        a[2] += s["seconds"]
        a[3] += s["self_s"]
    lines = [f"set-up: {rep['seconds']:.2f} s, {rep['unnamed_s']:.2f} s of it under no span"
             + (f" ({rep['dropped']['rows']} rows, {rep['dropped']['compiles']} compiles dropped)"
                if any(rep["dropped"].values()) else ""),
             f"{'stage':<30}{'n':>4}{'seconds':>10}{'self':>10}"]
    lines += [f"{'  ' * d + name:<30}{n:>4}{secs:>10.2f}{self_s:>10.2f}"
              for name, (d, n, secs, self_s) in agg.items()]
    lines.append(f"{'program (kind, k)':<30}{'trace':>8}{'lower':>8}{'backend':>9}  cache")
    for p in rep["programs"][:5]:
        hit = {True: "hit", False: "miss"}.get(p.get("cache_hit"), "-")
        label = f"{p['program']} ({p.get('kind')}, {p.get('k')})"
        lines.append(f"{label:<30}{p.get('trace_s', 0.0):>8.2f}{p.get('lower_s', 0.0):>8.2f}"
                     f"{p.get('backend_s', 0.0):>9.2f}  {hit}")
    o = rep["other"]
    lines.append(f"{'other x' + str(o['count']):<30}{o['trace_s']:>8.2f}{o['lower_s']:>8.2f}"
                 f"{o['backend_s']:>9.2f}  {o['cache_hits']} hits; "
                 + ", ".join(f"{n} x{k} {s:.2f}" for n, k, s in o["by_name"][:3]))
    return "\n".join(lines)


SETUP = SetupLedger()
jax.monitoring.register_event_duration_secs_listener(SETUP._on_duration)
jax.monitoring.register_event_listener(SETUP._on_event)


def setup_report(since: Optional[float] = None, until: Optional[float] = None) -> dict:
    """Why did this process take so long to start: ``stages`` (the set-up
    spans' rows in order, each with its ``self_s`` and the ``compile_s`` jax
    spent inside it), ``programs`` (the ``program.acquire`` rows, dearest
    first by trace + lowering + backend seconds), ``other`` (the compiles no
    acquisition claimed, summed, with the five dearest names), ``compiles``
    (the compile ledger's rows), ``unnamed_s`` (seconds of ``[since, until]``
    under no span) and ``text``, a table of it.  Times are
    ``time.perf_counter``'s; the interval defaults to first row .. last end."""
    return SETUP.report(since, until)


class SetupSpan(RecordEvent, contextlib.ContextDecorator):
    """A ``RecordEvent`` of the set-up path: the same span in a trace, and a
    row in ``SETUP`` when it ends.  ``note`` adds what is known only after
    the work (bytes, parameters) to the row.  Also a decorator, a fresh span
    a call: ``@SetupSpan("engine.init")``."""

    def __init__(self, name: str, *, ledger: Optional[SetupLedger] = None, **attrs):
        super().__init__(name, **attrs)
        self.ledger = SETUP if ledger is None else ledger
        self.id = self.parent = self.row = self._start = None

    def _recreate_cm(self):
        return type(self)(self.name, ledger=self.ledger, **self._attrs)

    def note(self, **attrs):
        self._attrs.update(attrs)

    def begin(self):
        spans = self.ledger._local.spans
        self.id = next(self.ledger._ids)
        self.parent = spans[-1].id if spans else None
        spans.append(self)
        self._start = self.ledger.clock()
        super().begin()

    def end(self):
        super().end()
        seconds = self.ledger.clock() - self._start
        self.ledger._local.spans.remove(self)
        self.row = self.ledger._append(self.id, self.parent, self.name, dict(self._attrs),
                                       self._start, seconds)


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


class Profiler:
    def __init__(self, *, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready: Optional[Callable] = None, timer_only: bool = False,
                 record_shapes: bool = False, profile_memory: bool = False,
                 with_flops: bool = False, emit_nvtx: bool = False):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(*scheduler) if isinstance(scheduler, (tuple, list)) else None
        )
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._host_events = []
        self._jax_active = False
        self._logdir = os.environ.get("PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
        self._step_times = []
        self._last_step_t = None

    # ---- lifecycle ----
    def start(self):
        RecordEvent._active_sink = self._host_events
        self._last_step_t = time.perf_counter()
        self._transition(self._scheduler(self._step) if self._scheduler else ProfilerState.RECORD)

    def stop(self):
        if self._jax_active:
            jax.profiler.stop_trace()
            self._jax_active = False
        RecordEvent._active_sink = None
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def _transition(self, new_state: ProfilerState):
        if self._timer_only:
            self._state = new_state
            return
        if new_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) and not self._jax_active:
            os.makedirs(self._logdir, exist_ok=True)
            jax.profiler.start_trace(self._logdir)
            self._jax_active = True
        if new_state == ProfilerState.CLOSED and self._jax_active:
            jax.profiler.stop_trace()
            self._jax_active = False
        self._state = new_state

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append((now - self._last_step_t, num_samples))
        self._last_step_t = now
        self._step += 1
        if self._scheduler:
            self._transition(self._scheduler(self._step))

    def step_info(self, unit: str = "samples") -> str:
        if not self._step_times:
            return "no steps recorded"
        dts = [d for d, _ in self._step_times[-10:]]
        avg = sum(dts) / len(dts)
        info = f"avg step {avg*1e3:.2f} ms"
        samples = [n for _, n in self._step_times[-10:] if n]
        if samples:
            ips = sum(samples) / sum(dts)
            info += f", ips {ips:.2f} {unit}/s"
        return info

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # ---- reporting ----
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False, time_unit="ms", views=None):
        agg = defaultdict(lambda: [0, 0.0])
        for name, _, dt in self._host_events:
            agg[name][0] += 1
            agg[name][1] += dt
        lines = ["-" * 64, f"{'Event':<36}{'Calls':>8}{'Total(ms)':>12}", "-" * 64]
        for name, (calls, total) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<36}{calls:>8}{total*1e3:>12.3f}")
        if self._step_times:
            lines.append("-" * 64)
            lines.append(f"steps: {len(self._step_times)}  {self.step_info()}")
        out = "\n".join(lines)
        print(out)
        return out

    def _export_host_events(self, path: str):
        events = [
            {"name": name, "ph": "X", "pid": 0, "tid": 0,
             "ts": t0 * 1e6, "dur": dt * 1e6}
            for name, t0, dt in self._host_events
        ]
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)

    def export(self, path: str, format: str = "json"):  # noqa: A002
        self._export_host_events(path)


class benchmark:
    """parity: paddle.profiler.benchmark timer (timer.py) — begin/step/end."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._times = []
        self._t = None

    def begin(self):
        self._t = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t is not None:
            self._times.append((now - self._t, num_samples))
        self._t = now

    def end(self):
        self._t = None

    def report(self):
        if not self._times:
            return {}
        dts = [d for d, _ in self._times]
        rep = {"avg_step_s": sum(dts) / len(dts), "steps": len(dts)}
        samples = [n for _, n in self._times if n]
        if samples:
            rep["ips"] = sum(samples) / sum(dts)
        return rep


class SortedKeys:
    """Sort keys for summary tables (parity: profiler.SortedKeys)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


def export_protobuf(path):
    raise NotImplementedError(
        "protobuf trace export: use Profiler(timer_only=False) chrome-trace "
        "export (perfetto-compatible), the XLA-native trace format")


def load_profiler_result(filename):
    import json

    with open(filename) as f:
        return json.load(f)
