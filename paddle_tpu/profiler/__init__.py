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
looped model), and on a mixed launch ``prefill_rows``, the rows it feeds
prompt chunks),
``engine.wait``, ``engine.harvest`` (stats: what the model's trunk counted
in the launch, ``attn_positions_live`` / ``attn_positions_read`` /
``attn_rows_kernel`` and the cache write's ``kv_write_tokens`` /
``kv_write_blocks`` for a dense paged cache, ``moe_tokens`` /
``moe_local_picks`` for expert layers, ``loop_tokens`` /
``loop_token_passes`` for a looped trunk: tokens fed, and tokens x passes run,
``dsa_queries`` / ``dsa_positions_scored`` / ``dsa_positions_selected`` /
``dsa_positions_read`` for learned sparse attention: ONE layer's, over the
live queries whose context exceeds the model's ``index_topk``, beside
``attn_positions_live``, the context of every row fed);
``train_step.call`` (stats ``step``, ``steps``).

Device scopes (``jax.named_scope``: metadata in the compiled program, nothing
at run time), one vocabulary for every model family:
``embed``, ``attn_proj``, ``paged_attention`` > ``rope`` ``kv_write`` (on the
chip the write is the ``paged_write`` kernel inside it) and,
under ``while/body/`` once for each loop around them (row tiles or chunk
rows, then context blocks), ``kv_gather`` ``scores`` ``values`` (on the
chip a one-token row is in none of the three: it attends inside the
``paged_decode`` kernel); ``attn_out``,
``mlp``, ``norm``, ``head``, ``sample``, ``scan_carry`` (the serving
programs); ``post_norm`` (a sandwich block's norm on a sublayer's output);
``loop_pass`` > ``while/body/`` the layers' scopes, ``norm``, ``exit_gate``
(one pass of a looped trunk, itself the body of the loop over the passes);
``latent_proj``, ``latent_attention`` > ``kv_write`` and the three under
``while/body/`` (a latent cache), ``router``, ``experts``, ``shared_expert``
(expert layers); ``indexer`` > ``index_proj``, ``index_write``,
``while/body/`` {``index_gather``, ``index_scores``}, ``index_topk`` (the
selector of learned sparse attention, ops/sparse_index.py);
``attention`` >
``flash_attention``, ``loss``, ``optimizer``, ``grad_unscale`` (the train
step, which shares ``embed`` ``attn_proj`` ``attn_out`` ``mlp`` ``norm``
``head``).

Kernels (``pallas_call(name=)``, the name of the custom call's device event):
``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, ``rms_norm``,
``rms_norm_residual``, ``fused_rope``, ``swiglu_fwd``, ``swiglu_bwd``,
``int8_matmul``, ``paged_decode``, ``paged_write``.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from collections import defaultdict
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "make_scheduler", "export_chrome_tracing",
    "RecordEvent", "benchmark", "SummaryView",
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
