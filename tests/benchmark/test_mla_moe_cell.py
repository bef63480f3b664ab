"""The ``mla_moe`` family's part of the benchmark on the CPU: its cost
functions on hand-worked shapes, its three readers on a small trace written
out by hand, and a tiny cell of it end to end through benchmark.run's
functions, sound and under the control."""
import argparse
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import loader
from benchmark.harness import mla_moe_cost as cost
from benchmark.harness.program_trace import ProgramTrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_mla_moe")
CELL = "openpangu718b.serve.doc-batch"


@pytest.fixture(scope="module")
def cfg():
    return loader.load_cell(CELL).config


# ------------------------------------------------------------------- shapes
def test_parameters_are_the_issues_arithmetic(cfg):
    assert cost.attention_params(cfg) == 196_575_232          # 196.58 M a layer
    assert cost.expert_params(cfg) == 47_185_920              # 47.19 M, routed or shared
    assert cost.layer_counts(cfg) == (1, 4) and cost.router_outputs(cfg) == 256
    assert cost.head_params(cfg) == 7680 * 19200
    dense_ffn = 3 * 7680 * 18432
    assert cost.trunk_params(cfg) == (5 * 196_575_232 + dense_ffn
                                      + 4 * (7680 * 256 + 47_185_920))
    # with the 16 held experts a layer, the embedding and the head: 4.919 B
    total = cost.trunk_params(cfg) + 4 * 16 * 47_185_920 + 2 * cost.head_params(cfg)
    assert abs(total / 4.919e9 - 1) < 0.001
    assert cost.latent_bytes_per_token(cfg) == 5 * 1152 == 5760


def test_an_iterations_bytes_and_a_launchs_flops(cfg):
    assert cost.held_experts_hit(cfg, 0) == 0.0
    assert cost.held_experts_hit(cfg, 1) == pytest.approx(16 * 8 / 256)
    assert cost.held_experts_hit(cfg, 10_000) == pytest.approx(16.0)
    fixed = 2 * (cost.trunk_params(cfg) + cost.head_params(cfg))
    assert cost.iteration_bytes(cfg, 0, 0) == fixed
    assert (cost.iteration_bytes(cfg, 10_000, 1000) - fixed
            == pytest.approx(4 * 16 * 94_371_840 + 1000 * 5760))
    assert cost.attention_flops_per_position(cfg) == 2 * 128 * 320 * 5
    assert cost.launch_flops(cfg, 1, 0, 0, 0) == 2.0 * cost.trunk_params(cfg)
    assert (cost.launch_flops(cfg, 0, 3, 2, 7)
            == 2.0 * 47_185_920 * 3 + 2.0 * cost.head_params(cfg) * 2 + 81_920 * 5 * 7)


# ------------------------------------------------------------------ readers
class _Cell:
    name = "no.such.cell"


class _Req:
    def __init__(self, n):
        self.prompt = [1] * n


def _trace(counts=True):
    """Two scan launches inside a 10 us window (a third starts before it):
    a mixed one of 8 iterations and a decode-only one of 4."""
    harvest = ({"moe_tokens": 4 * 500, "moe_local_picks": 900},
               {"moe_tokens": 4 * 260, "moe_local_picks": 500}) if counts else ({}, {})
    host = [("engine.launch", 900, 50, {"kind": "mixed", "k": 8, "launch": 1}),
            ("engine.harvest", 4100, 100, harvest[0]),
            ("engine.launch", 4900, 50, {"kind": "mega", "k": 4, "launch": 2}),
            ("engine.harvest", 8100, 100, harvest[1]),
            ("engine.launch", 8900, 50, {"kind": "step", "k": 1, "launch": 3})]
    modules = [("jit_mixed", -2000, 2500), ("jit_mixed", 1000, 3000), ("jit_mega", 5000, 3000),
               ("jit_step", 9000, 500)]
    return ProgramTrace(window=(0, 10_000), host=host, modules=modules, ops=[])


def _run(cfg, program_trace, **kw):
    return dict({"trace": object(), "cell": _Cell(), "program_trace": program_trace,
                 "config": cfg,
                 "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                 "counters": {"megasteps": 10, "megastep_tokens": 400},
                 "live_tokens_mean": 100_000.0, "requests": [_Req(1000), _Req(3000)]}, **kw)


def _read(metric, run):
    return loader.load_module("layer_metrics", metric).read(run)


def test_launch_means_read_the_harvest_spans(cfg):
    means = cost.launch_means(_run(cfg, _trace()))
    assert means == {"k": 6.0, "seconds": 3e-6, "moe_tokens": 1520.0, "moe_local_picks": 700.0}
    assert cost.mean_prefill_position(_run(cfg, _trace())) == (1e6 + 9e6) / (2 * 4000)


def test_the_three_readers_on_a_trace_written_by_hand(cfg):
    run = _run(cfg, _trace())
    assert _read("expert_rows_per_iteration", run) == pytest.approx(700 / (4 * 16 * 6))
    tokens = 1520 / 4 / 6
    hbm = _read("scan_hbm_share.mla_moe", run)
    assert hbm == pytest.approx(
        100 * cost.iteration_bytes(cfg, tokens, 100_000.0) / 819e9 / (3e-6 / 6))
    attended = 100_000.0 * 6 + (380 - 40) * 1250.0
    flops = _read("scan_flops_share.mla_moe", run)
    assert flops == pytest.approx(
        100 * cost.launch_flops(cfg, 380, 700, 40, attended) / (197e12 * 3e-6))


@pytest.mark.parametrize("metric", ["expert_rows_per_iteration", "scan_hbm_share.mla_moe",
                                    "scan_flops_share.mla_moe"])
def test_a_program_without_the_counts_gives_nothing(cfg, metric):
    """The parent commit's engine opens ``engine.harvest`` without them, and
    a dense model's has nothing to count: the line leaves the metric out."""
    assert _read(metric, _run(cfg, _trace(counts=False))) is None
    assert _read(metric, _run(cfg, None, trace=None)) is None
    assert _read(metric, {}) is None


# ------------------------------------------------ a tiny cell, end to end
def _measure(tmp_path, *, control=0, seconds=1.5, seed=2**31 + 29):
    from benchmark.harness.compile_meter import CompileMeter
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cell = loader.load_cell("tiny.mla-moe.docs", root=FIXTURE)
    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds, trace=0,
                              control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return json.loads(bench_run.measure(cell, args, device, CompileMeter(), str(tmp_path)))


def test_a_tiny_cell_runs_end_to_end_and_is_correct(tmp_path):
    out = _measure(tmp_path)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_control_in_int8_is_not_correct(tmp_path):
    out = _measure(tmp_path, control=1, seconds=3.0)
    assert out["correct"] is False and out["attempted"] > 0


def test_the_new_cells_name_files_and_traffic_as_the_issue_gives_them():
    bench = loader.load_benchmark()
    dh = loader.load_cell("mistral7b.serve.decode-heavy")
    db = loader.load_cell(CELL)
    assert dh.config_name == "mistral-7b-v0.3.serve1" and dh.chips == db.chips == 1
    t = dh.traffic
    assert (t["clients"], t["ramp_completions"], t["first_wave"]) == (32, 4, 0.05)
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 128, "sigma": 0.5,
                                    "min": 64, "max": 256}
    assert t["sizes"]["new_tokens"] == {"dist": "uniform", "min": 1024, "max": 2048}
    t = db.traffic
    assert (t["clients"], t["ramp_completions"], t["first_wave"]) == (128, 32, 0.05)
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 0.7,
                                    "min": 256, "max": 8192}
    assert t["sizes"]["new_tokens"] == {"dist": "lognormal", "median": 160, "sigma": 0.6,
                                        "min": 32, "max": 512}
    for cell in (dh, db):
        assert cell.traffic["sizes"]["count"] == 32 and cell.traffic["generator"] == "closed_loop"
        assert cell.traffic["sampling"] == {"temperature": 0.0}
        longest = cell.traffic["sizes"]["prompt"]["max"] + cell.traffic["sizes"]["new_tokens"]["max"]
        assert longest <= cell.config["engine"]["max_seq_len"]
    names = lambda cell: {m["name"] for m in cell.per_layer}          # noqa: E731
    assert "scan_hbm_share" in names(dh) and "scan_hbm_share" not in names(db)
    # the family's own three readers are files; BENCHMARK.json cannot list them
    # yet (tests/benchmark/test_program_trace.py pins the list: PERF.md section 7)
    for metric in ("expert_rows_per_iteration", "scan_hbm_share.mla_moe",
                   "scan_flops_share.mla_moe"):
        assert callable(loader.load_module("layer_metrics", metric).read)
    assert names(db) == names(dh) - {"scan_hbm_share"}
    assert [c["name"] for c in bench["configs"]][-1] == db.config_name
    assert db.config["engine"]["max_batch_size"] == 64 and db.config["n_routed_experts"] == 16
