"""The readers of the program's own spans, scopes and kernel names
(benchmark/harness/program_trace.py) on a small recorded trace, and the
BENCHMARK.json entries that PR 24 added with their modules."""
import json
import os

import pytest

from benchmark.harness import flash_cost, loader, program_trace
from benchmark.harness.program_trace import ProgramTrace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = loader.load_benchmark()
ACCEPTED_BEFORE = 11          # per-layer entries the benchmark had before PR 24
NEW = BENCH["per_layer"][ACCEPTED_BEFORE:]
MIXED = ("jit_mixed",)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_program_trace.json")) as f:
        d = json.load(f)
    return {k: ProgramTrace.from_dict(d[k]) for k in ("serve", "train")}


class _Cell:
    name = "no.such.cell"


def _run(trace, **kw):
    """A run as ``benchmark.run`` hands it to a metric, its program trace
    already loaded."""
    return dict({"trace": object(), "cell": _Cell(), "program_trace": trace}, **kw)


def _read(metric, run):
    return loader.load_module("layer_metrics", metric).read(run)


def test_a_recorded_trace_round_trips(recorded):
    again = ProgramTrace.from_dict(json.loads(json.dumps(recorded["serve"].to_dict())))
    assert again == recorded["serve"]
    assert program_trace.modules_in(again, MIXED) == [(1000, 4000), (5000, 8000)]


def test_idle_time_goes_to_the_innermost_span_instant_by_instant(recorded):
    launches, by_span = program_trace.gaps_by_span(recorded["serve"], program_trace.ENGINE_MODULES)
    assert launches == 2
    assert {k: round(v * 1e9) for k, v in by_span.items()} == {
        "caller": 1300, "frontend.step": 160, "frontend.dispatch": 180,
        "frontend.deliver": 330, "engine.step": 130, "engine.admit": 150,
        "engine.schedule": 420, "engine.launch": 370, "engine.wait": 150,
        "engine.harvest": 710}


@pytest.mark.parametrize("metric,idle_ns", [
    ("launch_gap_ms", 3900), ("launch_gap_ms.schedule", 570), ("launch_gap_ms.launch", 370),
    ("launch_gap_ms.harvest", 860), ("launch_gap_ms.frontend", 670)])
def test_launch_gap_and_its_parts(recorded, metric, idle_ns):
    assert _read(metric, _run(recorded["serve"])) == pytest.approx(idle_ns / 2 / 1e6)


def test_a_program_without_spans_gives_the_gap_and_no_part(recorded):
    """The parent of PR 24: module events and no span of the program."""
    bare = ProgramTrace.from_dict(dict(recorded["serve"].to_dict(), host=[]))
    assert _read("launch_gap_ms", _run(bare)) == pytest.approx(3900 / 2 / 1e6)
    for part in ("schedule", "launch", "harvest", "frontend"):
        assert _read(f"launch_gap_ms.{part}", _run(bare)) is None
    assert program_trace.iterations(bare, MIXED, 4) == 2 * 4


def test_scope_seconds_skip_containers_and_count_iterations(recorded):
    tr = recorded["serve"]
    sec = lambda scopes: round(program_trace.scope_seconds(tr, MIXED, scopes) * 1e9)  # noqa: E731
    assert sec(["paged_attention"]) == 3000
    assert sec(["paged_attention/kv_gather"]) == 2000
    assert sec(["mlp", "head"]) == 2000 and sec(["kv", "attention"]) == 0
    assert sec(None) == 1000                      # the copies; never the while
    assert program_trace.iterations(tr, MIXED, 4) == 16    # k of the launch spans
    assert program_trace.scope_seconds(tr, ("jit_mega",), ["mlp"]) == 0


@pytest.mark.parametrize("path,want", [
    ("jit(step)/jit(main)/jvp(loss)/reduce_sum", "forward"),
    ("jit(step)/jit(main)/transpose(jvp(loss))/mul", "backward"),
    ("jit(step)/jit(main)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/dot_general",
     "backward"),
    ("jit(step)/jit(main)/optimizer/mul", None), ("", None)])
def test_direction_of_an_operations_path(path, want):
    assert program_trace.direction(path) == want


def test_kernels_by_name_and_the_train_steps_metrics(recorded):
    tr = recorded["train"]
    per_step = program_trace.kernel_seconds(tr, flash_cost.KERNELS, ("jit_step",))
    assert [{k: (n, round(s * 1e9)) for k, (n, s) in per.items()} for per in per_step] == 2 * [
        {"flash_fwd": (2, 600), "flash_bwd_dq": (1, 400), "flash_bwd_dkv": (1, 500)}]
    cfg = {"num_attention_heads": 2, "head_dim": 4, "hidden_size": 8}
    run = _run(tr, config=cfg, traffic={"batch": 1, "seq": 8}, peaks={"bf16_flops": 1e13})
    assert _read("step_flash_ms", run) == pytest.approx(1500 / 1e6)
    assert _read("step_host_ms", run) == pytest.approx(250 / 1e6)
    # 2 * 1 * 2 * 8 * 8 * 4 = 1024 a forward; two forwards and one backward
    assert flash_cost.step_flops(cfg, run["traffic"], 2, 1) == 1024 * 4.5
    assert _read("flash_roofline_share", run) == pytest.approx(100 * 4608 / 1e13 / 1500e-9)
    # kernels without names (the parent's program): nothing to read
    unnamed = ProgramTrace.from_dict(dict(tr.to_dict(), ops=[
        [s.replace("flash_", "checkpoint_"), p, a, d] for s, p, a, d in tr.ops]))
    assert _read("step_flash_ms", _run(unnamed)) is None
    assert _read("flash_roofline_share", _run(unnamed, config=cfg, traffic=run["traffic"],
                                              peaks=run["peaks"])) is None


def test_flash_flops_at_the_cells_shapes():
    # PERF.md: B 4, H 32, S 2048, D 128 -> 1.37e11 a forward kernel
    assert flash_cost.forward_flops(4, 32, 2048, 128) == pytest.approx(1.374e11, rel=1e-3)


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_a_new_entry_has_a_module_a_cell_list_and_reads_nothing_without_a_trace(entry, tmp_path):
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    read = loader.load_module("layer_metrics", entry["name"]).read
    assert read({}) is None and read({"trace": None, "cell": _Cell()}) is None
    # traced, but the profiler left no file where benchmark.run has it write
    assert read({"trace": object(), "cell": _Cell()}) is None
    assert program_trace.load(str(tmp_path)) is None


def test_the_new_entries_are_the_ones_the_program_can_feed():
    assert [m["name"] for m in NEW] == [
        "launch_gap_ms", "launch_gap_ms.schedule", "launch_gap_ms.launch",
        "launch_gap_ms.harvest", "launch_gap_ms.frontend", "step_host_ms",
        "step_flash_ms", "flash_roofline_share"]
    assert len(BENCH["per_layer"]) == ACCEPTED_BEFORE + len(NEW)
