"""The ``looped_dense`` family's part of the benchmark on the CPU: the
parameter and byte arithmetic of ISSUE 30 on the published shapes, its three
readers on a small trace written out by hand, a tiny cell of it end to end
through benchmark.run's functions, sound and under the control, and the cell,
its files and its traffic as the issue names them."""
import argparse
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import loader
from benchmark.harness import looped_cost as cost
from benchmark.harness.program_trace import ProgramTrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_looped")
CELL = "ouro2.6b.serve.reason-batch"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    return loader.load_cell(CELL).config


# ------------------------------------------------------------------- shapes
def test_parameters_are_the_issues_arithmetic(cfg):
    p = cost.parameters(cfg)
    assert cost.layer_matmul_params(cfg) == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert p["layer"] == 51_388_416 and p["layers"] == 2_466_643_968
    assert p["embed"] == p["head"] == 100_663_296 and p["norm_and_gate"] == 4_097
    assert p["total"] == 2_667_974_657                   # 5.34 GB in bf16
    assert cost.passes(cfg) == 4 and cost.cache_layers(cfg) == 192
    assert cost.cache_bytes_per_position(cfg) == 2 * 16 * 128 * 2 == 8_192
    assert cost.cache_bytes_per_token(cfg) == 1_572_864  # 1.5 MiB
    e = cfg["engine"]
    tokens = e["num_blocks"] * e["block_size"]
    assert tokens == 6_144 and tokens * cost.cache_bytes_per_token(cfg) == 9_663_676_416
    # a block over the 192 cache layers
    assert e["block_size"] * cost.cache_bytes_per_token(cfg) == 100_663_296


def test_an_iterations_bytes_and_a_launchs_flops(cfg):
    weights = 4 * 48 * cost.layer_matmul_params(cfg) * 2
    head = 2048 * 49152 * 2
    assert cost.iteration_bytes(cfg, 0) == weights + head
    assert round(weights / 1e9, 1) == 19.7 and round(head / 1e9, 1) == 0.2
    assert round((weights + head) / 819e9 * 1e3, 1) == 24.3          # ms at the peak
    assert cost.iteration_bytes(cfg, 5000) - weights - head == 5000 * 1_572_864
    assert cost.launch_flops(cfg, 1, 0, 0) == 2.0 * 4 * 48 * cost.layer_matmul_params(cfg)
    assert cost.launch_flops(cfg, 0, 3, 7) == 2.0 * 2048 * 49152 * 3 + 4.0 * 16 * 128 * 192 * 7
    # a model of one pass (the Mistral cells): its own layers, once
    dense = loader.load_cell("mistral7b.serve.batch").config
    assert cost.passes(dense) == 1 and cost.cache_layers(dense) == dense["num_hidden_layers"]
    assert cost.cache_bytes_per_position(dense) == 2 * 8 * 128 * 2


# ------------------------------------------------------------------ readers
class _Cell:
    name = "no.such.cell"


def _trace(counts=True, kernel=True):
    """Three launches inside a 10 us window (a fourth starts before it): a
    mixed scan of 8 iterations, a decode-only one of 4 whose module event
    holds three ``paged_decode`` events, and a prefill step."""
    def harvest(tokens, read, rows):
        h = {"attn_positions_live": read - 10, "attn_positions_read": read,
             "attn_rows_kernel": rows}
        return dict(h, loop_tokens=tokens, loop_token_passes=4 * tokens) if counts else h

    host = [("engine.harvest", 100, 50, harvest(1, 1, 1)),              # its launch is outside
            ("engine.launch", 900, 50, {"kind": "mixed", "k": 8, "launch": 1, "passes": 4}),
            ("engine.harvest", 4100, 100, harvest(130, 9000, 80)),
            ("engine.launch", 4900, 50, {"kind": "mega", "k": 4, "launch": 2, "passes": 4}),
            ("engine.harvest", 8100, 100, harvest(48, 6400, 48)),
            ("engine.launch", 8900, 50, {"kind": "step", "k": 1, "launch": 3, "passes": 4}),
            ("engine.harvest", 9600, 100, harvest(200, 0, 0))]
    modules = [("jit_mixed", -2000, 2500), ("jit_mixed", 1000, 3000), ("jit_mega", 5000, 3000),
               ("jit_step", 9000, 500)]
    ops = [("custom-call:paged_decode.1", "", 1500, 400),               # in the mixed scan
           ("fusion.7", "", 5100, 300)]
    if kernel:
        ops += [("custom-call:paged_decode.1", "", 5500 + 600 * i, 200) for i in range(3)]
    return ProgramTrace(window=(0, 10_000), host=host, modules=modules, ops=ops)


def _run(cfg, program_trace, **kw):
    return dict({"trace": object(), "cell": _Cell(), "program_trace": program_trace,
                 "config": cfg, "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                 "live_tokens_mean": 4000.0}, **kw)


def _read(metric, run):
    return loader.load_module("layer_metrics", metric).read(run)


def test_launches_pair_each_launch_with_its_harvest(cfg):
    got = cost.launches(_run(cfg, _trace()))
    assert [(l["kind"], l["k"]) for l in got] == [("mixed", 8), ("mega", 4), ("step", 1)]
    assert got[1]["counts"]["attn_positions_read"] == 6400 and got[1]["t0"] == 4900
    means = cost.scan_means(_run(cfg, _trace()))
    assert means == {"iter_s": 6e-6 / 12, "loop_tokens": 178, "loop_token_passes": 712}


def test_the_three_readers_on_a_trace_written_by_hand(cfg):
    run = _run(cfg, _trace())
    assert _read("passes_per_token", run) == 4.0
    assert _read("scan_hbm_share.looped", run) == pytest.approx(
        100 * cost.iteration_bytes(cfg, 4000.0) / 819e9 / (6e-6 / 12))
    # the decode-only launch alone: 6400 positions of one cache layer, 192 of them
    assert _read("paged_decode_hbm_share", run) == pytest.approx(
        100 * 6400 * 8192 * 192 / 819e9 / 600e-9)


@pytest.mark.parametrize("metric", ["scan_hbm_share.looped", "passes_per_token"])
def test_a_program_without_the_loops_counts_gives_nothing(cfg, metric):
    """The parent commit's engine counts no passes, and a model of one pass
    has none to count: the line leaves the metric out."""
    assert _read(metric, _run(cfg, _trace(counts=False))) is None
    assert _read(metric, _run(cfg, None, trace=None)) is None
    assert _read(metric, {}) is None


def test_the_kernels_share_reads_a_model_of_one_pass_and_nothing_without_the_kernel(cfg):
    dense = loader.load_cell("mistral7b.serve.batch").config
    got = _read("paged_decode_hbm_share", _run(dense, _trace(counts=False)))
    assert got == pytest.approx(100 * 6400 * 4096 * 12 / 819e9 / 600e-9)
    assert _read("paged_decode_hbm_share", _run(cfg, _trace(kernel=False))) is None
    assert _read("paged_decode_hbm_share", _run(cfg, None, trace=None)) is None
    assert _read("paged_decode_hbm_share", {}) is None


# ------------------------------------------------ a tiny cell, end to end
def _measure(tmp_path, *, control=0, seconds=1.5, seed=2**31 + 30):
    from benchmark.harness.compile_meter import CompileMeter
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cell = loader.load_cell("tiny.looped.reason", root=FIXTURE)
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


def test_the_bf16_witness_lies_between_float32_and_the_control(capsys):
    """The reference run as a script: itself in bf16 and in W8A8 against its
    float32 self, by the check's own statistic.  bf16 is off float32 (the
    witness sees the precision) and the control is further off than it."""
    ref = loader.load_module("references", "looped_dense")
    ref._witness(["--root", FIXTURE, "--config", "tiny.looped", "--seed", "2147493111",
                  "--sequences", "4"])
    lines = {r["witness"]: r for r in map(json.loads, capsys.readouterr().out.splitlines())}
    assert set(lines) == {"bf16", "int8"} and lines["bf16"]["tokens"] > 300
    assert 0 < lines["bf16"]["mean_gap_nats"] < lines["int8"]["mean_gap_nats"]
    assert lines["bf16"]["argmax_agree"] > lines["int8"]["argmax_agree"]


# ------------------------------------------------------- names and numbers
def test_the_cell_its_files_and_its_traffic_are_as_the_issue_names_them(cfg):
    bench = loader.load_benchmark()
    cell = loader.load_cell(CELL)
    assert cell.config_name == "ouro-2.6b.serve1" and cell.traffic_name == "reason-batch"
    assert cell.chips == 1
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == [] == list(cfg["reduced"])
    assert entry["file"] == "benchmark/configs/ouro-2.6b.serve1.json"
    assert (cfg["family"], cfg["path"], cfg["chips"]) == ("looped_dense", "serve", 1)
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_completions"], t["first_wave"]) == (
        "closed_loop", 32, 8, 0.05)
    assert t["sizes"]["count"] == 32 and t["sampling"] == {"temperature": 0.0}
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 128, "sigma": 0.5,
                                    "min": 64, "max": 256}
    assert t["sizes"]["new_tokens"] == {"dist": "uniform", "min": 192, "max": 448}
    seeds = {loader.load_cell(w["name"]).traffic.get("sizes", {}).get("seed")
             for w in bench["workloads"] if w["name"] != CELL}
    assert t["sizes"]["seed"] not in seeds                       # a seed of its own
    e = cfg["engine"]
    assert (e["max_seq_len"], e["megastep_k"]) == (768, 8)
    # the budget is the issue's; the slots are 12 of its 16, cut as it asks
    # where the pool is short (the file's ``memory`` says why)
    assert e["token_budget"] == 256
    assert e["max_batch_size"] == 12 and "slots" in cfg["memory"]
    assert t["sizes"]["prompt"]["max"] + t["sizes"]["new_tokens"]["max"] <= e["max_seq_len"]
    assert e["block_size"] % 16 == 0 and e["num_blocks"] * e["block_size"] == 6_144
    names = {m["name"] for m in cell.per_layer}
    assert names == {"host_share.batch", "tokens_per_launch", "kv_pool_live_share",
                     "scan_iter_ms", "device_idle_share.batch", "peak_hbm_gb", "launch_gap_ms",
                     "launch_gap_ms.schedule", "launch_gap_ms.launch", "launch_gap_ms.harvest",
                     "launch_gap_ms.frontend"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # the family's own three readers are files; BENCHMARK.json cannot list them
    # yet (tests/benchmark/test_program_trace.py pins the list: PERF.md section 7)
    for metric in ("scan_hbm_share.looped", "passes_per_token", "paged_decode_hbm_share"):
        assert callable(loader.load_module("layer_metrics", metric).read)
    assert len(bench["per_layer"]) == 19


def test_the_cells_of_pr_26_are_still_as_their_issue_gave_them():
    """``test_mla_moe_cell.py``'s test of names, line for line but its last pin
    (``configs[-1]``), which no PR that appends a configuration can keep and
    ``conftest.py`` expects to fail: here openPangu's entry keeps its PLACE."""
    bench = loader.load_benchmark()
    dh = loader.load_cell("mistral7b.serve.decode-heavy")
    db = loader.load_cell("openpangu718b.serve.doc-batch")
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
    for metric in ("expert_rows_per_iteration", "scan_hbm_share.mla_moe",
                   "scan_flops_share.mla_moe"):
        assert callable(loader.load_module("layer_metrics", metric).read)
    assert names(db) == names(dh) - {"scan_hbm_share"}
    # the list as PR 26 left it, in its order, and this PR's entry after it
    listed = [c["name"] for c in bench["configs"]]
    assert listed[:3] == ["mistral-7b-v0.3.serve1", "mistral-7b-v0.3.train1", db.config_name]
    assert "ouro-2.6b.serve1" in listed[3:]
    assert db.config["engine"]["max_batch_size"] == 64 and db.config["n_routed_experts"] == 16


def test_published_is_the_catalogs_row_key_by_key(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Ouro-2.6B")
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == row["config"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key                  # nothing is cut: reduced is empty
    assert cfg["reduced"] == {} and len(cfg["assumed"]) >= 7
    assert (cfg["total_ut_steps"], cfg["early_exit_threshold"]) == (4, 1)
