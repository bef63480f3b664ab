"""The ``swa_gqa_moe`` family's part of the benchmark on the CPU: the parameter,
byte and block arithmetic of ISSUE 45 on the published shapes, its five readers
on a small trace written out by hand, on a trace recorded on the chip and on an
empty run, a tiny cell of it end to end through benchmark.run's functions, sound
and under the controls, and the cell, its files and its traffic as the issue
names them.

Written with MEMBERSHIP only: no ``[-1]``, no length of ``configs``,
``workloads`` or ``per_layer``, so that the next configuration's PR does not turn
it red."""
import argparse
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import loader
from benchmark.harness import swa_moe_cost as cost
from benchmark.harness import traffic as traffic_sizes
from benchmark.harness.program_trace import ProgramTrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_swa_moe")
CELL = "smallthinker21b.serve.mixed-length"
CONFIG = "smallthinker-21b-a3b.serve1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers"]
READERS = ("window_read_share", "window_blocks_released_per_iteration",
           "scan_hbm_share.swa_moe", "scan_flops_share.swa_moe",
           "expert_rows_per_iteration.swa_moe")
ELEVEN = {"host_share.batch", "tokens_per_launch", "kv_pool_live_share", "scan_iter_ms",
          "device_idle_share.batch", "peak_hbm_gb", "launch_gap_ms", "launch_gap_ms.schedule",
          "launch_gap_ms.launch", "launch_gap_ms.harvest", "launch_gap_ms.frontend"}


@pytest.fixture(scope="module")
def cfg():
    return loader.load_cell(CELL).config


# ------------------------------------------------------------------- shapes
def test_parameters_are_the_issues_arithmetic(cfg):
    assert cost.layer_counts(cfg) == {"global": 3, "window": 9}
    # the heads' width is not the hidden size: 28 x 128 = 3,584
    assert cost.attention_params(cfg) == 2 * 2560 * 3584 + 2 * 2560 * 512 == 20_971_520
    assert cost.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240 and cost.experts_held(cfg) == 64
    layer = 20_971_520 + 2560 * 64 + 64 * 5_898_240
    assert layer == 398_622_720                                       # the issue's 398.6 M
    parts = cost.parameters(cfg)
    assert parts["embed"] == parts["head"] == 151_936 * 2560 == 388_956_160
    assert parts["trunk"] + parts["experts"] == 12 * layer
    assert parts["total"] == 5_561_448_960                            # the issue's 5,561 M
    assert "5,561 M" in cfg["reduced"]["num_hidden_layers"]
    assert "11.12 GB" in cfg["reduced"]["num_hidden_layers"]
    # a position is 2,048 B a cache layer; a block 384 KiB of the global pool, 1.125 MiB
    # of the window pool
    e = cfg["engine"]
    assert cost.cache_bytes_per_position(cfg) == 2048
    assert 64 * 2048 * 3 == 384 * 1024 and 64 * 2048 * 9 == 1152 * 1024
    assert e["num_blocks"] == {"global": 3456, "window": 2560}
    # a row holds of the window pool what overlaps its window and ONE launch's reach
    assert cost.hold_cap(cfg) == -(-(4096 + 512) // 64) + 1 == 73
    assert e["max_batch_size"] * cost.hold_cap(cfg) > e["num_blocks"]["window"]   # by the mix
    # without the release the same rows would hold every position in twelve layers
    assert 12 * 2048 == 4 * (3 * 2048) and 16384 // 64 == 256 > 3 * cost.hold_cap(cfg)


def test_the_pools_cover_what_the_mixs_fixed_sequence_reserves(cfg):
    """The engine reserves a row's worst hold of the window pool at admission and
    the queue's head waits while a pool is short: the benchmark's window opens
    only when every slot is taken, so both pools must cover ANY 48 consecutive
    requests of the mix's one fixed sequence (the closed loop sends them round
    and round; rows leave out of order, so this is the mean's neighbourhood and
    the host-side replay in PERF.md section 4 the bound)."""
    cell = loader.load_cell(CELL)
    e, sizes = cfg["engine"], traffic_sizes.sizes(cell.traffic, cell.traffic["sizes"]["count"])
    need = [-(-(p + n) // e["block_size"]) for p, n in sizes]
    cap = cost.hold_cap(cfg)
    assert max(p + n for p, n in sizes) <= e["max_seq_len"]
    for at in range(len(need)):
        rows = [need[(at + i) % len(need)] for i in range(e["max_batch_size"])]
        assert sum(rows) <= e["num_blocks"]["global"], at
        assert sum(min(r, cap) for r in rows) <= e["num_blocks"]["window"], at
    past = sum(p > 4096 for p, _ in sizes) / len(sizes)
    assert 0.2 <= past <= 0.3 and min(p for p, _ in sizes) == 128


def test_the_configurations_memory_is_the_compilers_and_over_the_floor(cfg):
    mem = cfg["memory"]
    said = mem["compiled_for_v5e"]
    assert set(said) == {"step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4",
                         "mega_K8"}
    e = cfg["engine"]
    pools = 64 * 2048 * (3 * e["num_blocks"]["global"] + 9 * e["num_blocks"]["window"])
    held = 2 * cost.parameters(cfg)["total"] + pools
    for kind, m in said.items():
        assert 0 <= m["arguments"] - held - 2 * 16384 * 64 * 4 < 2 ** 20, kind    # rope; control
        assert m["arguments"] < m["live"] <= m["arguments"] + m["temporaries"] + 2 ** 21
    fullest = max(v["live"] for v in said.values())
    assert 0.9 * mem["bytes_limit"] < fullest < mem["bytes_limit"] - 1.2e9
    assert "11.12 GB" in mem["arithmetic"] and "4.38 GB" in mem["arithmetic"]


def test_an_iterations_bytes_and_a_launchs_flops(cfg):
    fixed = 2 * (cost.trunk_params(cfg) + cost.head_params(cfg))
    assert cost.iteration_bytes(cfg, 0, 0, 0, 0) == fixed
    assert cost.iteration_bytes(cfg, 0, 100, 0, 0) - fixed == 100 * 2 * 5_898_240
    # 1,000 positions attended in a global layer, 600 in a window layer, 50 tokens written
    assert (cost.iteration_bytes(cfg, 50, 0, 1000, 600) - fixed
            == (3 * 1000 + 9 * 600 + 12 * 50) * 2048)
    assert cost.launch_flops(cfg, 1, 0, 0, 0, 0) == 2.0 * cost.trunk_params(cfg)
    assert (cost.launch_flops(cfg, 0, 3, 2, 11, 7)
            == 2.0 * 5_898_240 * 3 + 2.0 * 388_956_160 * 2 + 4.0 * 28 * 128 * (3 * 11 + 9 * 7))
    # an iteration that touches every expert moves the whole 10.3 GB outside the table
    whole = cost.iteration_bytes(cfg, 512, 12 * 64, 0, 0)
    assert abs(whole / 10.36e9 - 1) < 0.01


# ------------------------------------------------------------------ readers
class _Cell:
    name = "no.such.cell"


def _trace(counts=True):
    """Three launches inside a 10 us window (a fourth starts before it): a mixed
    scan of 8 iterations, a decode-only one of 4, and a prefill step."""
    def harvest(tokens, touched, live, read, spared, released):
        h = {"moe_tokens": 12 * tokens, "moe_local_picks": 72 * tokens}
        if counts:
            h.update({"experts_touched": touched, "expert_tile_rows": 96 * tokens,
                      "expert_tile_rows_live": 72 * tokens, "kv_write_tokens": tokens,
                      "attn_positions_live.global": live, "attn_positions_read.global": live + 640,
                      "attn_positions_live.window": live, "attn_positions_read.window": read,
                      "window_positions_spared": spared, "window_blocks_released": released,
                      "window_blocks_held": 1700})
        return h

    host = [("engine.harvest", 100, 50, harvest(1, 1, 9, 9, 0, 90)),          # its launch is outside
            ("engine.launch", 900, 50, {"kind": "mixed", "k": 8, "launch": 1, "passes": 1}),
            ("engine.harvest", 4100, 100, harvest(3000, 6000, 900_000, 640_000, 310_000, 100)),
            ("engine.launch", 4900, 50, {"kind": "mega", "k": 4, "launch": 2, "passes": 1}),
            ("engine.harvest", 8100, 100, harvest(190, 2900, 500_000, 330_000, 200_000, 104)),
            ("engine.launch", 8900, 50, {"kind": "step", "k": 1, "launch": 3, "passes": 1}),
            ("engine.harvest", 9600, 100, harvest(500, 700, 700, 1024, 0, 107))]
    modules = [("jit_mixed", -2000, 2500), ("jit_mixed", 1000, 3000), ("jit_mega", 5000, 3000),
               ("jit_step", 9000, 500)]
    return ProgramTrace(window=(0, 10_000), host=host, modules=modules, ops=[])


def _run(cfg, program_trace, **kw):
    return dict({"trace": object(), "cell": _Cell(), "program_trace": program_trace,
                 "config": cfg, "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                 "counters": {"megasteps": 10, "megastep_tokens": 400},
                 "live_tokens_mean": 60_000.0}, **kw)


def _read(metric, run):
    return loader.load_module("layer_metrics", metric).read(run)


def test_scan_sums_read_the_scans_harvest_spans(cfg):
    sums = cost.scan_sums(_run(cfg, _trace()))
    assert sums == {"launches": 2, "k": 12, "seconds": 6e-6, "moe_tokens": 12 * 3190,
                    "moe_local_picks": 72 * 3190, "experts_touched": 8900,
                    "expert_tile_rows": 96 * 3190, "expert_tile_rows_live": 72 * 3190,
                    "kv_write_tokens": 3190, "window_positions_spared": 510_000,
                    "attn_positions_live.global": 1_400_000,
                    "attn_positions_read.global": 1_401_280,
                    "attn_positions_live.window": 1_400_000,
                    "attn_positions_read.window": 970_000}
    assert cost.attended(sums) == (1_400_000, 890_000)
    # the span carries the engine's total as it STARTS: 100 -> 107 over the mixed
    # scan's 8 iterations and the decode scan's 4 (the step's own harvest comes after)
    assert cost.blocks_released(_run(cfg, _trace())) == (7, 12)


def test_the_five_readers_on_a_trace_written_by_hand(cfg):
    run = _run(cfg, _trace())
    assert _read("window_read_share", run) == pytest.approx(100 * 970_000 / 1_400_000)
    assert _read("window_blocks_released_per_iteration", run) == pytest.approx(7 / 12)
    nbytes = cost.iteration_bytes(cfg, 3190 / 12, 8900 / 12, 1_400_000 / 12, 890_000 / 12)
    assert _read("scan_hbm_share.swa_moe", run) == pytest.approx(
        100 * nbytes / 819e9 / (6e-6 / 12))
    flops = cost.launch_flops(cfg, 3190, 72 * 3190, 2 * 40, 1_400_000, 890_000)
    assert _read("scan_flops_share.swa_moe", run) == pytest.approx(
        100 * flops / (197e12 * 6e-6))
    assert _read("expert_rows_per_iteration.swa_moe", run) == pytest.approx(
        72 * 3190 / (12 * 64 * 12))


def test_the_five_readers_on_a_run_recorded_on_the_chip(cfg):
    """``fixture_swa_moe/recorded_swa_moe_trace.json``: the window, the launch and
    harvest spans and the module events (no operations) of one traced run of the
    cell on a TPU v5e (PR 45, seed 3000000452): 18 mixed launches of 8 iterations
    in 4.97 s, 470 tokens an iteration of which 7 rows' chunks.  The readings are
    that run's own result line's (PERF.md section 5)."""
    d = json.load(open(os.path.join(FIXTURE, "recorded_swa_moe_trace.json")))
    recorded = ProgramTrace(window=tuple(d["window"]),
                            host=[(n, s, dur, dict(st)) for n, s, dur, st in d["host"]],
                            modules=[tuple(m) for m in d["modules"]], ops=[])
    run = _run(cfg, recorded, counters={"megasteps": 1, "megastep_tokens": 171.2517006802721})
    sums = cost.scan_sums(run)
    assert (sums["launches"], sums["k"]) == (18, 144) and 4.6 < sums["seconds"] < 5.0
    assert sums["moe_tokens"] == 12 * sums["kv_write_tokens"]
    assert sums["moe_local_picks"] == sums["expert_tile_rows_live"] == 6 * sums["moe_tokens"]
    assert sums["experts_touched"] == 12 * 64 * 144                 # every expert, every iteration
    assert sums["attn_positions_live.global"] == sums["attn_positions_live.window"]
    assert sums["attn_positions_read.window"] < sums["attn_positions_live.window"] \
        < sums["attn_positions_read.global"]
    assert _read("window_read_share", run) == pytest.approx(73.95, abs=0.01)
    assert _read("window_blocks_released_per_iteration", run) == pytest.approx(1.404, abs=0.001)
    assert _read("expert_rows_per_iteration.swa_moe", run) == pytest.approx(42.12, abs=0.01)
    assert _read("scan_hbm_share.swa_moe", run) == pytest.approx(45.92, abs=0.01)
    assert _read("scan_flops_share.swa_moe", run) == pytest.approx(9.80, abs=0.01)


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_kinds_of_cache_layer_gives_nothing(cfg, metric):
    """The parent commit's engine has no such counts, and a model of one kind has
    nothing to count: the line leaves the metric out."""
    assert callable(loader.load_module("layer_metrics", metric).read)
    assert _read(metric, _run(cfg, _trace(counts=False))) is None
    assert _read(metric, _run(cfg, None, trace=None)) is None
    assert _read(metric, {}) is None


# ------------------------------------------------ a tiny cell, end to end
def _measure(tmp_path, *, control=0, seconds=1.5, seed=2**31 + 45):
    from benchmark.harness.compile_meter import CompileMeter
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cell = loader.load_cell("tiny.swa-moe.mixed", root=FIXTURE)
    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds, trace=0,
                              control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return json.loads(bench_run.measure(cell, args, device, CompileMeter(), str(tmp_path)))


def test_a_tiny_cell_runs_end_to_end_and_is_correct(tmp_path):
    out = _measure(tmp_path)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_controls_are_not_correct(tmp_path, capsys):
    """``--control 1``: the W8A8 reference decides, and the three that make the
    MECHANISM wrong (the window forgotten, RoPE on the global layers, the router
    reading the expert layer's own input) are read beside it: each lies over
    both limits."""
    out = _measure(tmp_path, control=1, seconds=3.0)
    assert out["correct"] is False and out["attempted"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{") and '"gaps"' in l]
    gaps = {n["gaps"]: n for n in notes}
    assert set(gaps) == {"served", "int8", "window_off", "rope_all", "router_post"}
    limits = loader.load_cell("tiny.swa-moe.mixed", root=FIXTURE).config["check"]["limits"]
    assert gaps["served"]["max"] < limits["max_gap_nats"]
    for low in ("int8", "window_off", "rope_all", "router_post"):
        assert gaps[low]["mean"] > limits["mean_gap_nats"], low
        assert gaps[low]["max"] > limits["max_gap_nats"], low


# ------------------------------------------------------- names and numbers
def test_the_cell_its_files_and_its_traffic_are_as_the_issue_names_them(cfg):
    bench = loader.load_benchmark()
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (CONFIG, "mixed-length", 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert sum(c["name"] == CONFIG for c in bench["configs"]) == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1      # no second cell
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) > names.index("lfm2-24b-a2b.serve1")        # appended behind
    assert cells.index(CELL) > cells.index("lfm2-24b.serve.chat-batch")
    assert entry["reduced"] == REDUCED == list(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"]
    assert (cfg["family"], cfg["path"], cfg["chips"]) == ("swa_gqa_moe", "serve", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, work))
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_completions"], t["first_wave"]) == (
        "closed_loop", 96, 32, 0.05)
    assert t["sizes"]["count"] == 64 and t["sizes"]["seed"] == 20260945
    assert t["sampling"] == {"temperature": 0.0}
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 1.2,
                                    "min": 128, "max": 14336}
    assert t["sizes"]["new_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                                        "min": 32, "max": 512}
    seeds = {loader.load_cell(w["name"]).traffic.get("sizes", {}).get("seed")
             for w in bench["workloads"] if w["name"] != CELL}
    assert t["sizes"]["seed"] not in seeds                       # a seed of its own
    e = cfg["engine"]
    assert e == {"max_batch_size": 48, "max_seq_len": 16384, "block_size": 64,
                 "token_budget": 512, "num_blocks": {"global": 3456, "window": 2560},
                 "megastep_k": 8}
    assert t["clients"] == 2 * e["max_batch_size"]               # a prompt always waits
    assert t["sizes"]["prompt"]["max"] + t["sizes"]["new_tokens"]["max"] <= e["max_seq_len"]
    assert e["max_seq_len"] == cfg["max_position_embeddings"] == cfg["check"]["pad_to"]
    assert t["sizes"]["prompt"]["max"] > 3 * cfg["sliding_window_size"]
    assert cfg["check"]["max_tokens"] >= 12_000 + 8_192      # the longest, and one of 8 k beside it
    assert cfg["control"] == dict(cfg["control"], reference_precision="int8", also_read=[
        "bf16", "window_off", "rope_all", "router_post"])
    assert {m["name"] for m in cell.per_layer} == ELEVEN
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # the family's own five readers are files; BENCHMARK.json cannot list them
    # yet (three tests pin the list's length: PERF.md section 7)
    listed = {m["name"] for m in bench["per_layer"]}
    for metric in READERS:
        assert callable(loader.load_module("layer_metrics", metric).read)
        assert metric not in listed
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    return next(r for r in map(json.loads, open(CATALOG))
                if r["name"] == "SmallThinker-21BA3B-Instruct")


def test_published_is_the_catalogs_row_key_by_key(cfg):
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == row["config"]
    assert len(cfg["assumed"]) >= 5 and "four pipeline stages" in cfg["stands_for"]
    for key in ("rope", "window", "router", "router_precision", "weights"):
        assert key in cfg["assumed"], key


@pytest.mark.parametrize("key", sorted(json.loads(open(
    os.path.join(loader.ROOT, "benchmark", "configs", CONFIG + ".json")).read())["published"]))
def test_every_key_outside_reduced_is_as_published(cfg, key):
    if key in REDUCED:
        assert cfg[key] != cfg["published"][key] and key in cfg["reduced"]
    else:
        assert cfg[key] == cfg["published"][key], key


def test_the_cut_keeps_every_width_and_the_guides_floors(cfg):
    for key in ("hidden_size", "head_dim", "moe_ffn_hidden_size", "num_attention_heads",
                "num_key_value_heads", "moe_num_primary_experts",
                "moe_num_active_primary_experts", "vocab_size", "sliding_window_size",
                "sliding_window_layout", "rope_layout"):
        assert key not in REDUCED and cfg[key] == cfg["published"][key]
    assert "experts_held" not in cfg and cfg["moe_num_primary_experts"] == 64 >= 8
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]) == (12, 52)
    # three WHOLE periods of the published layout, 1 global : 3 window
    assert cfg["sliding_window_layout"][:12] == cfg["rope_layout"][:12] == [0, 1, 1, 1] * 3
    assert len(cfg["sliding_window_layout"]) == 52
    assert cfg["head_dim"] * cfg["num_attention_heads"] == 3584 != cfg["hidden_size"]


def test_the_configuration_builds_the_programs_model(cfg):
    import math

    family = loader.load_module("families", cfg["family"])
    mc = family.model_config(cfg)
    assert (mc.moe_num_primary_experts, mc.experts_held, mc.head_dim) == (64, (0, 64), 128)
    assert mc.layers_of(False) == [0, 4, 8] and len(mc.layers_of(True)) == 9
    assert not mc.tie_word_embeddings and mc.dtype == "bfloat16"
    assert [mc.roped(i) for i in range(4)] == [False, True, True, True]
    layer, outer = family.leaf_shapes(cfg)
    count = lambda d: sum(math.prod(s) for s in d.values())  # noqa: E731
    assert 12 * count(layer) + count(outer) == cost.parameters(cfg)["total"]
