"""The ``parallel_swa_moe`` family's part of the benchmark on the CPU: the
parameter, byte and block arithmetic of ISSUE 48 on the published shapes, its
readers on a small trace written out by hand, on a trace recorded on the chip and
on an empty run, a tiny cell of it end to end through benchmark.run's functions,
sound and under the controls, and the cell, its files and its traffic as the
issue names them.

Written with MEMBERSHIP only: no ``[-1]``, no length of ``configs``,
``workloads`` or ``per_layer``, so that the next configuration's PR does not turn
it red."""
import argparse
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import loader
from benchmark.harness import parallel_moe_cost as cost
from benchmark.harness import traffic as traffic_sizes
from benchmark.harness.program_trace import ProgramTrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_parallel_moe")
CELL = "commandaplus.serve.rag-batch"
CONFIG = "command-a-plus-05-2026.serve1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# this family's own four, and the two of PR 45's that read its counters as they are
OWN = ("scan_hbm_share.parallel_moe", "scan_flops_share.parallel_moe",
       "expert_rows_per_iteration.parallel_moe", "shared_expert_flops_share")
READERS = OWN + ("window_read_share", "window_blocks_released_per_iteration")
SHARES = tuple(r for r in READERS if "share" in r)
ELEVEN = {"host_share.batch", "tokens_per_launch", "kv_pool_live_share", "scan_iter_ms",
          "device_idle_share.batch", "peak_hbm_gb", "launch_gap_ms", "launch_gap_ms.schedule",
          "launch_gap_ms.launch", "launch_gap_ms.harvest", "launch_gap_ms.frontend"}
CONTROLS = ("int8", "serial_block", "shared_sum", "rope_all", "window_off", "misplaced")


@pytest.fixture(scope="module")
def cfg():
    return loader.load_cell(CELL).config


# ------------------------------------------------------------------- shapes
def test_parameters_are_the_issues_arithmetic(cfg):
    assert cost.layer_counts(cfg) == {"global": 1, "window": 3}
    # the heads' width is FOUR times the hidden size: 128 x 128 = 16,384
    assert cost.attention_params(cfg) == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert cost.expert_params(cfg) == 3 * 4096 * 4096 == 50_331_648
    assert cost.shared_params(cfg) == 4 * 50_331_648 == 201_326_592
    assert cost.experts_held(cfg) == 16 and cost.router_outputs(cfg) == 128
    layer = 142_606_336 + 4096 * 128 + 201_326_592 + 16 * 50_331_648
    assert layer == 1_149_763_584                                     # the issue's 1,149.8 M
    parts = cost.parameters(cfg)
    assert parts["embed"] == 32768 * 4096 == 134_217_728 and "head" not in parts   # tied: once
    assert parts["trunk"] + parts["experts"] == 4 * layer
    assert parts["total"] == 4_733_292_544                            # the issue's 4,733 M
    assert "4,733 M" in cfg["reduced"]["vocab_size"] and "9.47 GB" in cfg["reduced"]["vocab_size"]
    # the published count: 32 layers of 344.5 M beside 128 experts, and the table of 262,144
    whole = 32 * (142_606_336 + 4096 * 128 + 201_326_592 + 128 * 50_331_648) + 262144 * 4096
    active = 32 * (142_606_336 + 4096 * 128 + 201_326_592 + 8 * 50_331_648) + 262144 * 4096
    assert round(whole / 1e9, 1) == 218.3 and round(active / 1e9, 1) == 25.0
    # a position is 4 KiB a cache layer; a block 256 KiB of the global pool, 768 KiB of
    # the window pool
    e = cfg["engine"]
    assert cost.cache_bytes_per_position(cfg) == 4096
    assert e["num_blocks"] == {"global": 12288, "window": 2336}
    # a row holds of the window pool what overlaps its window and ONE launch's reach
    assert cost.hold_cap(cfg) == -(-(4096 + 512) // 64) + 1 == 73
    assert e["max_batch_size"] * cost.hold_cap(cfg) == e["num_blocks"]["window"]


def test_the_pools_cover_what_the_mixs_fixed_sequence_reserves(cfg):
    """The engine reserves a row's worst hold of the window pool at admission and
    every block of the global pool a request will need: both pools must cover ANY
    32 of the mix's one fixed sequence of 64 (rows leave out of order), and the
    global pool covers all 64 at once, so the slots and never a pool are the
    limit."""
    cell = loader.load_cell(CELL)
    e, sizes = cfg["engine"], traffic_sizes.sizes(cell.traffic, cell.traffic["sizes"]["count"])
    need = sorted(-(-(p + n) // e["block_size"]) for p, n in sizes)
    assert max(p + n for p, n in sizes) <= e["max_seq_len"]
    assert sum(need) == 10_171 <= e["num_blocks"]["global"]
    assert sum(need[-e["max_batch_size"]:]) <= e["num_blocks"]["global"]
    assert e["max_batch_size"] * cost.hold_cap(cfg) <= e["num_blocks"]["window"]
    # the seed's draw: 4 of 64 under the window, 29 past 8 k, 11 past 16 k, none at the cap
    prompts = [p for p, _ in sizes]
    assert (sum(p < 4096 for p in prompts), sum(p > 8192 for p in prompts),
            sum(p > 16384 for p in prompts), max(prompts), min(prompts)) == (4, 29, 11, 27939, 2167)


def test_the_configurations_memory_is_the_compilers_and_over_the_floor(cfg):
    mem = cfg["memory"]
    said = mem["compiled_for_v5e"]
    assert set(said) == {"step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4",
                         "mega_K8"}
    e = cfg["engine"]
    pools = 64 * 4096 * (1 * e["num_blocks"]["global"] + 3 * e["num_blocks"]["window"])
    held = 2 * cost.parameters(cfg)["total"] + pools
    for kind, m in said.items():
        assert 0 <= m["arguments"] - held - 2 * 33536 * 64 * 4 < 2 ** 20, kind    # rope; control
        assert m["arguments"] < m["live"] <= m["arguments"] + m["temporaries"] + 2 ** 21
    fullest = max(v["live"] for v in said.values())
    assert 0.8 * mem["bytes_limit"] < fullest < mem["bytes_limit"] - 1.0e9
    assert "9.47 GB" in mem["arithmetic"] and "5.06 GB" in mem["arithmetic"]


def test_an_iterations_bytes_and_a_launchs_flops(cfg):
    fixed = 2 * (cost.trunk_params(cfg) + cost.head_params(cfg))
    assert cost.iteration_bytes(cfg, 0, 0, 0, 0) == fixed
    assert cost.iteration_bytes(cfg, 0, 10, 0, 0) - fixed == 10 * 2 * 50_331_648
    # 1,000 positions attended in the global layer, 600 in a window layer, 50 tokens written
    assert (cost.iteration_bytes(cfg, 50, 0, 1000, 600) - fixed
            == (1 * 1000 + 3 * 600 + 4 * 50) * 4096)
    assert cost.launch_flops(cfg, 4, 0, 0, 0, 0) == 2.0 * cost.trunk_params(cfg)   # one token
    assert (cost.launch_flops(cfg, 0, 3, 2, 11, 7)
            == 2.0 * 50_331_648 * 3 + 2.0 * 134_217_728 * 2 + 4.0 * 128 * 128 * (1 * 11 + 3 * 7))
    assert cost.shared_flops(cfg, 4) == 2.0 * 4 * 201_326_592
    # an iteration that touches every held expert moves the whole 9.47 GB
    whole = cost.iteration_bytes(cfg, 0, 4 * 16, 0, 0)
    assert abs(whole / 9.466e9 - 1) < 0.001


# ------------------------------------------------------------------ readers
class _Cell:
    name = "no.such.cell"


def _trace(counts=True):
    """Three launches inside a 10 us window (a fourth starts before it): a mixed
    scan of 8 iterations, a decode-only one of 4, and a prefill step."""
    def harvest(tokens, touched, live, read, spared, released):
        h = {"moe_tokens": 4 * tokens, "moe_local_picks": 4 * tokens}
        if counts:
            h.update({"experts_touched": touched,
                      "expert_tile_rows": 8 * tokens, "expert_tile_rows_live": 4 * tokens,
                      "kv_write_tokens": tokens,
                      "attn_positions_live.global": live, "attn_positions_read.global": live + 640,
                      "attn_positions_live.window": live, "attn_positions_read.window": read,
                      "window_positions_spared": spared, "window_blocks_released": released,
                      "window_blocks_held": 1700})
        return h

    host = [("engine.harvest", 100, 50, harvest(1, 1, 9, 9, 0, 90)),          # its launch is outside
            ("engine.launch", 900, 50, {"kind": "mixed", "k": 8, "launch": 1, "passes": 1}),
            ("engine.harvest", 4100, 100, harvest(3000, 500, 900_000, 640_000, 310_000, 100)),
            ("engine.launch", 4900, 50, {"kind": "mega", "k": 4, "launch": 2, "passes": 1}),
            ("engine.harvest", 8100, 100, harvest(190, 240, 500_000, 330_000, 200_000, 104)),
            ("engine.launch", 8900, 50, {"kind": "step", "k": 1, "launch": 3, "passes": 1}),
            ("engine.harvest", 9600, 100, harvest(500, 60, 700, 1024, 0, 107))]
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
    assert sums == {"launches": 2, "k": 12, "seconds": 6e-6, "moe_tokens": 4 * 3190,
                    "moe_local_picks": 4 * 3190, "experts_touched": 740,
                    "expert_tile_rows": 8 * 3190, "expert_tile_rows_live": 4 * 3190,
                    "kv_write_tokens": 3190, "window_positions_spared": 510_000,
                    "attn_positions_live.global": 1_400_000,
                    "attn_positions_read.global": 1_401_280,
                    "attn_positions_live.window": 1_400_000,
                    "attn_positions_read.window": 970_000}
    assert cost.attended(sums) == (1_400_000, 890_000)
    assert cost.sampled_rows(_run(cfg, _trace()), sums) == 2 * 40


def test_the_readers_on_a_trace_written_by_hand(cfg):
    run = _run(cfg, _trace())
    nbytes = cost.iteration_bytes(cfg, 3190 / 12, 740 / 12, 1_400_000 / 12, 890_000 / 12)
    assert _read("scan_hbm_share.parallel_moe", run) == pytest.approx(
        100 * nbytes / 819e9 / (6e-6 / 12))
    flops = cost.launch_flops(cfg, 4 * 3190, 4 * 3190, 2 * 40, 1_400_000, 890_000)
    assert _read("scan_flops_share.parallel_moe", run) == pytest.approx(
        100 * flops / (197e12 * 6e-6))
    assert _read("expert_rows_per_iteration.parallel_moe", run) == pytest.approx(
        4 * 3190 / (4 * 16 * 12))
    assert _read("shared_expert_flops_share", run) == pytest.approx(
        100 * 2.0 * 201_326_592 * 4 * 3190 / flops)
    # PR 45's two read this family's counters as they are
    assert _read("window_read_share", run) == pytest.approx(100 * 970_000 / 1_400_000)
    assert _read("window_blocks_released_per_iteration", run) == pytest.approx(7 / 12)


def _recorded():
    d = json.load(open(os.path.join(FIXTURE, "recorded_parallel_moe_trace.json")))
    trace = ProgramTrace(window=tuple(d["window"]),
                         host=[(n, s, dur, dict(st)) for n, s, dur, st in d["host"]],
                         modules=[tuple(m) for m in d["modules"]], ops=[])
    return d, trace


def test_the_readers_on_a_run_recorded_on_the_chip(cfg):
    """``fixture_parallel_moe/recorded_parallel_moe_trace.json``: the window, the
    launch and harvest spans and the module events (no operations) of one traced
    run of the cell on a TPU v5e (PR 48, seed 3000000610: 21 mixed launches of 8
    iterations in 4.93 s, 450 tokens an iteration; its counters and the readings
    ``.proof/record_trace.py`` took on the chip are in the file).  Every reader gives that run's own reading, and
    every share lies under 100."""
    d, recorded = _recorded()
    run = _run(cfg, recorded, counters=d["counters"])
    sums = cost.scan_sums(run)
    assert sums["launches"] > 5 and sums["k"] == 8 * sums["launches"] and sums["seconds"] > 1.0
    assert sums["moe_tokens"] == 4 * sums["kv_write_tokens"]
    # 8 picks a token of 128 experts, 16 held: an eighth of them fall here
    assert 0.9 < sums["moe_local_picks"] / sums["moe_tokens"] < 1.1
    assert sums["moe_local_picks"] == sums["expert_tile_rows_live"]
    assert sums["experts_touched"] == 4 * 16 * sums["k"]            # every held expert, every iteration
    assert sums["attn_positions_live.global"] == sums["attn_positions_live.window"]
    assert sums["attn_positions_read.window"] < sums["attn_positions_live.window"] \
        < sums["attn_positions_read.global"]
    for metric in READERS:
        got = _read(metric, run)
        assert got == pytest.approx(d["readings"][metric], rel=1e-6), metric
        if metric in SHARES:
            assert 0 < got < 100, metric


@pytest.mark.parametrize("metric", OWN)
def test_a_program_without_a_parallel_block_gives_nothing(cfg, metric):
    """A run without this family's counts, without a trace, or of ANOTHER
    family's configuration: the line leaves the metric out and nothing is raised."""
    assert callable(loader.load_module("layer_metrics", metric).read)
    assert _read(metric, _run(cfg, _trace(counts=False))) is None
    assert _read(metric, _run(cfg, None, trace=None)) is None
    assert _read(metric, {}) is None
    swa = json.load(open(os.path.join(HERE, "fixture_swa_moe", "recorded_swa_moe_trace.json")))
    other = ProgramTrace(window=tuple(swa["window"]),
                         host=[(n, s, dur, dict(st)) for n, s, dur, st in swa["host"]],
                         modules=[tuple(m) for m in swa["modules"]], ops=[])
    theirs = loader.load_cell("smallthinker21b.serve.mixed-length").config
    assert _read(metric, _run(theirs, other)) is None   # SmallThinker's recorded run


# ------------------------------------------------ a tiny cell, end to end
def _measure(tmp_path, *, control=0, seconds=1.5, seed=2**31 + 48):
    from benchmark.harness.compile_meter import CompileMeter
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cell = loader.load_cell("tiny.parallel-moe.rag", root=FIXTURE)
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
    """``--control 1``: the W8A8 reference decides, and the four that make the
    MECHANISM wrong (the block run serially, the shared experts summed, RoPE on
    the global layer, the window forgotten) are read beside it: each lies over
    both limits."""
    out = _measure(tmp_path, control=1, seconds=3.0)
    assert out["correct"] is False and out["attempted"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{") and '"gaps"' in l]
    gaps = {n["gaps"]: n for n in notes}
    assert set(gaps) == {"served"} | set(CONTROLS)
    limits = loader.load_cell("tiny.parallel-moe.rag", root=FIXTURE).config["check"]["limits"]
    assert gaps["served"]["max"] < limits["max_gap_nats"]
    for low in CONTROLS:
        assert gaps[low]["mean"] > limits["mean_gap_nats"], low
        assert gaps[low]["max"] > limits["max_gap_nats"], low


# ------------------------------------------------------- names and numbers
def test_the_cell_its_files_and_its_traffic_are_as_the_issue_names_them(cfg):
    bench = loader.load_benchmark()
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (CONFIG, "rag-batch", 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert sum(c["name"] == CONFIG for c in bench["configs"]) == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1      # no second cell
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) > names.index("smallthinker-21b-a3b.serve1")      # appended behind
    assert cells.index(CELL) > cells.index("smallthinker21b.serve.mixed-length")
    assert entry["reduced"] == REDUCED == list(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"]
    assert (cfg["family"], cfg["path"], cfg["chips"]) == ("parallel_swa_moe", "serve", 1)
    for x in bench["configs"] + bench["workloads"]:
        assert len(x["why"]) <= 200, x["name"]
    assert "8x their share" in work["why"] and "depth 4" in work["why"]
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_completions"], t["first_wave"]) == (
        "closed_loop", 48, 16, 0.05)
    assert t["sizes"]["count"] == 64 and t["sizes"]["seed"] == 20260948
    assert t["sampling"] == {"temperature": 0.0}
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 8192, "sigma": 0.7,
                                    "min": 2048, "max": 32768}
    assert t["sizes"]["new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.5,
                                        "min": 64, "max": 768}
    seeds = {loader.load_cell(w["name"]).traffic.get("sizes", {}).get("seed")
             for w in bench["workloads"] if w["name"] != CELL}
    assert t["sizes"]["seed"] not in seeds                       # a seed of its own
    e = cfg["engine"]
    assert e == {"max_batch_size": 32, "max_seq_len": 33536, "block_size": 64,
                 "token_budget": 512, "num_blocks": {"global": 12288, "window": 2336},
                 "megastep_k": 8}
    assert t["clients"] == 48 > e["max_batch_size"] == 32       # a prompt always waits
    assert t["sizes"]["prompt"]["max"] + t["sizes"]["new_tokens"]["max"] == e["max_seq_len"]
    assert e["max_seq_len"] == cfg["check"]["pad_to"] <= cfg["max_position_embeddings"]
    assert t["sizes"]["prompt"]["max"] == 8 * cfg["sliding_window"]
    assert cfg["check"]["max_tokens"] == 48_000 and cfg["check"]["sample_requests"] == 4
    assert cfg["control"] == dict(cfg["control"], reference_precision="int8", also_read=[
        "bf16", "serial_block", "shared_sum", "rope_all", "window_off", "misplaced"])
    assert {m["name"] for m in cell.per_layer} == ELEVEN
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # the family's own readers are files; BENCHMARK.json cannot list them yet (two
    # tests pin the list's names: PERF.md section 7 item 1)
    listed = {m["name"] for m in bench["per_layer"]}
    for metric in OWN:
        assert callable(loader.load_module("layer_metrics", metric).read)
        assert metric not in listed
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


def test_pr_45s_cell_is_still_as_its_issue_gave_it():
    bench = loader.load_benchmark()
    cell = loader.load_cell("smallthinker21b.serve.mixed-length")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "smallthinker-21b-a3b.serve1", "mixed-length", 1)
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cell.traffic["clients"] == 96 and cell.traffic["sizes"]["seed"] == 20260945
    assert cell.config["engine"]["num_blocks"] == {"global": 3456, "window": 2560}
    assert {m["name"] for m in cell.per_layer} == ELEVEN
    for name in ELEVEN | {"serve_tokens_per_s"}:
        m = next(m for m in bench["per_layer"] + bench["end_to_end"] if m["name"] == name)
        assert {cell.name, CELL} <= set(m["workloads"]), name
    hbm = next(m for m in bench["per_layer"] if m["name"] == "scan_hbm_share")
    assert CELL not in hbm["workloads"]                 # the dense family's cost model


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    return next(r for r in map(json.loads, open(CATALOG))
                if r["name"] == "command-a-plus-05-2026")


def test_published_is_the_catalogs_row_key_by_key(cfg):
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == row["config"]
    assert "64 chips" in cfg["stands_for"] and "EIGHT chips share each layer" in cfg["stands_for"]
    for key in ("shared_experts", "expert_width", "block", "norm", "rope", "window", "router",
                "head", "tower", "weights"):
        assert key in cfg["assumed"], key
    assert "218.3 B" in cfg["assumed"]["shared_experts"]


@pytest.mark.parametrize("key", sorted(json.loads(open(
    os.path.join(loader.ROOT, "benchmark", "configs", CONFIG + ".json")).read())["published"]))
def test_every_key_outside_reduced_is_as_published(cfg, key):
    if key in REDUCED:
        assert cfg[key] != cfg["published"][key] and key in cfg["reduced"]
    else:
        assert cfg[key] == cfg["published"][key], key


def test_the_cut_keeps_every_width_and_the_guides_floors(cfg):
    for key in ("hidden_size", "head_dim", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok", "num_shared_experts",
                "sliding_window", "layer_types", "rope_theta", "layer_norm_eps"):
        assert key not in REDUCED and cfg[key] == cfg["published"][key]
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["num_shared_experts"],
            cfg["router_outputs"], cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["rope_theta"], cfg["layer_norm_eps"]) == (
                4096, 128, 8, 128, 4096, 4, 128, 8, 4096, 50000, 1e-5)
    assert (cfg["num_experts"], cfg["published"]["num_experts"], cfg["experts_held"]) == (
        16, 128, [0, 16]) and cfg["num_experts"] >= 8
    assert (cfg["vocab_size"], cfg["published"]["vocab_size"]) == (32768, 262144)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]          # the floor: an eighth
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]) == (4, 32)
    # ONE whole period of the published layout, 3 window : 1 global
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert len(cfg["layer_types"]) == 32


def test_the_configuration_builds_the_programs_model(cfg):
    import math

    family = loader.load_module("families", cfg["family"])
    mc = family.model_config(cfg)
    assert (mc.num_experts, mc.experts_held, mc.head_dim, mc.vocab_size) == (
        128, (0, 16), 128, 32768)
    assert mc.layers_of(False) == [3] and mc.layers_of(True) == [0, 1, 2]
    assert mc.tie_word_embeddings and mc.dtype == "bfloat16"
    layer, outer = family.leaf_shapes(cfg)
    assert "head" not in outer                                # the table is the head
    count = lambda d: sum(math.prod(s) for s in d.values())  # noqa: E731
    assert 4 * count(layer) + count(outer) == cost.parameters(cfg)["total"]
