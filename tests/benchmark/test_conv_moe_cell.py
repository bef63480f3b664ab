"""The ``conv_gqa_moe`` family's part of the benchmark on the CPU: the parameter
and byte arithmetic of ISSUE 38 on the published shapes, its six readers on a
small trace written out by hand and on an empty run, a tiny cell of it end to
end through benchmark.run's functions, sound and under the controls, and the
cell, its files and its traffic as the issue names them.

Written with MEMBERSHIP only: no ``[-1]``, no length of ``configs``,
``workloads`` or ``per_layer``, so that the next configuration's PR does not
turn it red.  It also holds every assertion of
``test_dsa_cell.py::test_the_cell_its_files_and_its_traffic_are_as_the_issue_names_them``
but that test's pins of last place and of length, which no PR that brings a
configuration can keep (PERF.md section 7)."""
import argparse
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import conv_moe_cost as cost
from benchmark.harness import loader
from benchmark.harness.program_trace import ProgramTrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_conv_moe")
CELL = "lfm2-24b.serve.chat-batch"
CONFIG = "lfm2-24b-a2b.serve1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers"]
READERS = ("scan_hbm_share.conv_moe", "scan_flops_share.conv_moe", "expert_tile_fill",
           "experts_touched_share", "conv_rows_per_iteration",
           "expert_rows_per_iteration.conv_moe")
ELEVEN = {"host_share.batch", "tokens_per_launch", "kv_pool_live_share", "scan_iter_ms",
          "device_idle_share.batch", "peak_hbm_gb", "launch_gap_ms", "launch_gap_ms.schedule",
          "launch_gap_ms.launch", "launch_gap_ms.harvest", "launch_gap_ms.frontend"}


@pytest.fixture(scope="module")
def cfg():
    return loader.load_cell(CELL).config


# ------------------------------------------------------------------- shapes
def test_parameters_are_the_issues_arithmetic(cfg):
    assert cost.layer_counts(cfg) == {"conv": 8, "attention": 2, "dense": 2, "sparse": 8}
    assert cost.conv_params(cfg) == 2048 * 6144 + 2048 * 2048 + 6144 == 16_783_360
    assert cost.attention_params(cfg) == 10_485_760 and cost.head_dim(cfg) == 64
    assert cost.expert_params(cfg) == 9_437_184 and cost.experts_held(cfg) == 64
    dense_ffn = 3 * 2048 * 11776
    assert dense_ffn == 72_351_744
    assert cost.trunk_params(cfg) == (8 * 16_783_360 + 2 * 10_485_760 + 2 * dense_ffn
                                      + 8 * 2048 * 64) == 300_990_464
    parts = cost.parameters(cfg)
    assert parts["embed"] == 65_536 * 2048 and parts["head"] == 0          # tied
    assert parts["experts"] == 8 * 64 * 9_437_184
    assert parts["total"] == 5_267_090_176                                # the issue's 5,267 M
    assert "5,267 M" in cfg["reduced"]["num_hidden_layers"]
    assert "10.53 GB" in cfg["reduced"]["num_hidden_layers"]
    # the cache: 4,096 B a token, a pool of 131,072 tokens; the state: 64 KB a slot
    e = cfg["engine"]
    assert cost.cache_bytes_per_token(cfg) == 4096
    assert e["num_blocks"] * e["block_size"] == 131_072
    assert cost.state_bytes_per_slot(cfg) == 65_536
    assert cost.state_bytes_per_slot(cfg) * e["max_batch_size"] == 8_388_608
    # positional storage of the same inputs would be 32 KB a TOKEN
    assert 8 * 2048 * 2 == 8 * cost.cache_bytes_per_token(cfg)


def test_the_configurations_memory_is_the_compilers_and_over_the_floor(cfg):
    mem = cfg["memory"]
    said = mem["compiled_for_v5e"]
    assert set(said) == {"step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4",
                         "mega_K8"}
    # the arguments: weights, the head's copy, the pool, the state, rope, control
    e = cfg["engine"]
    held = (2 * (cost.parameters(cfg)["total"] - 8 * 64 * 4)              # bf16 ...
            + 4 * 8 * 64                                                  # ... but the bias
            + 2 * cost.head_params(cfg)
            + e["num_blocks"] * e["block_size"] * cost.cache_bytes_per_token(cfg)
            + e["max_batch_size"] * cost.state_bytes_per_slot(cfg))
    for kind, m in said.items():
        assert 0 <= m["arguments"] - held < 2 ** 21, kind                 # rope, control
        assert m["arguments"] < m["live"] <= m["arguments"] + m["temporaries"] + 2 ** 21
    fullest = max(v["live"] for v in said.values())
    assert 0.25 * mem["bytes_limit"] < fullest < mem["bytes_limit"] - 1.5e9
    assert "10.53 GB" in mem["arithmetic"] and "0.27 GB" in mem["arithmetic"]


def test_an_iterations_bytes_and_a_launchs_flops(cfg):
    fixed = 2 * (cost.trunk_params(cfg) + cost.head_params(cfg))
    assert cost.iteration_bytes(cfg, 0, 0, 0, 0) == fixed
    # 100 experts touched (of 8 x 64): their weights and no other's
    assert cost.iteration_bytes(cfg, 0, 100, 0, 0) - fixed == 100 * 2 * 9_437_184
    # 1,000 live positions read and 50 tokens written, in 2 attention layers
    assert cost.iteration_bytes(cfg, 50, 0, 1000, 0) - fixed == 1050 * 4096
    # 7 row-layers of state, read and written back
    assert cost.iteration_bytes(cfg, 0, 0, 0, 7) - fixed == 7 * 2 * 2 * 2048 * 2
    assert cost.launch_flops(cfg, 1, 0, 0, 0) == 2.0 * 300_990_464
    assert (cost.launch_flops(cfg, 0, 3, 2, 11)
            == 2.0 * 9_437_184 * 3 + 2.0 * 65_536 * 2048 * 2 + 4.0 * 32 * 64 * 2 * 11)
    # the issue's iteration: 512 tokens, 2,048 picks, every expert touched
    whole = cost.iteration_bytes(cfg, 512, 512, 0, 0)
    assert abs(whole / 10.5e9 - 1) < 0.02                                  # "the whole 10.5 GB"
    live = cost.launch_flops(cfg, 512, 2048, 134, 0)
    assert abs(live / 0.38e12 - 1) < 0.05       # 0.31 T experts + 0.04 head + the trunk


# ------------------------------------------------------------------ readers
class _Cell:
    name = "no.such.cell"


def _trace(counts=True):
    """Three launches inside a 10 us window (a fourth starts before it): a
    mixed scan of 8 iterations, a decode-only one of 4, and a prefill step."""
    def harvest(tokens, touched, tiles, rows, live):
        h = {"moe_tokens": 8 * tokens, "moe_local_picks": 32 * tokens}
        if counts:
            h.update(conv_rows_fed=8 * rows, experts_touched=touched,
                     expert_tile_rows=128 * tiles, expert_tile_rows_live=32 * tokens,
                     attn_positions_live=live, kv_write_tokens=tokens)
        return h

    host = [("engine.harvest", 100, 50, harvest(1, 1, 1, 1, 9)),       # its launch is outside
            ("engine.launch", 900, 50, {"kind": "mixed", "k": 8, "launch": 1, "passes": 1}),
            ("engine.harvest", 4100, 100, harvest(3000, 4000, 4100, 1040, 400_000)),
            ("engine.launch", 4900, 50, {"kind": "mega", "k": 4, "launch": 2, "passes": 1}),
            ("engine.harvest", 8100, 100, harvest(512, 1900, 1900, 512, 200_000)),
            ("engine.launch", 8900, 50, {"kind": "step", "k": 1, "launch": 3, "passes": 1}),
            ("engine.harvest", 9600, 100, harvest(500, 500, 500, 3, 700))]
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
    assert sums == {"launches": 2, "k": 12, "seconds": 6e-6, "conv_rows_fed": 8 * 1552,
                    "moe_tokens": 8 * 3512, "moe_local_picks": 32 * 3512,
                    "experts_touched": 5900, "expert_tile_rows": 128 * 6000,
                    "expert_tile_rows_live": 32 * 3512, "attn_positions_live": 600_000,
                    "kv_write_tokens": 3512}


def test_the_six_readers_on_a_trace_written_by_hand(cfg):
    run = _run(cfg, _trace())
    nbytes = cost.iteration_bytes(cfg, 3512 / 12, 5900 / 12, 600_000 / 12, 8 * 1552 / 12)
    assert _read("scan_hbm_share.conv_moe", run) == pytest.approx(
        100 * nbytes / 819e9 / (6e-6 / 12))
    flops = cost.launch_flops(cfg, 3512, 32 * 3512, 2 * 40, 600_000)
    assert _read("scan_flops_share.conv_moe", run) == pytest.approx(
        100 * flops / (197e12 * 6e-6))
    assert _read("expert_tile_fill", run) == pytest.approx(100 * 32 * 3512 / (128 * 6000))
    assert _read("experts_touched_share", run) == pytest.approx(100 * 5900 / (8 * 64 * 12))
    assert _read("conv_rows_per_iteration", run) == pytest.approx(1552 / 12)
    assert _read("expert_rows_per_iteration.conv_moe", run) == pytest.approx(
        32 * 3512 / (8 * 64 * 12))


def test_the_six_readers_on_a_run_recorded_on_the_chip(cfg):
    """``recorded_conv_moe_trace.json``: the window, the program's host spans
    and the module events (no operations) of one traced run of the cell on a
    TPU v5e (PR 38, seed 2900000397): 11 mixed launches of 8 iterations in
    4.94 s.  Each scan's module event STARTS 0.6-0.7 ms before its
    ``engine.launch`` span (the device's clock leads the host's), so the
    launches are matched by the events' middles."""
    d = json.load(open(os.path.join(HERE, "recorded_conv_moe_trace.json")))
    recorded = ProgramTrace(window=tuple(d["window"]),
                            host=[(n, s, dur, dict(st)) for n, s, dur, st in d["host"]],
                            modules=[tuple(m) for m in d["modules"]], ops=[])
    assert all(m[1] < l[1] for m, l in zip(
        recorded.modules, [h for h in recorded.host if h[0] == "engine.launch"]))
    run = _run(cfg, recorded, counters={"megasteps": 1, "megastep_tokens": 985.19})
    sums = cost.scan_sums(run)
    assert (sums["launches"], sums["k"]) == (11, 88) and sums["seconds"] == pytest.approx(4.837118)
    assert sums["kv_write_tokens"] == 22_970 and sums["moe_tokens"] == 8 * 22_970
    assert sums["moe_local_picks"] == sums["expert_tile_rows_live"] == 4 * sums["moe_tokens"]
    assert _read("scan_hbm_share.conv_moe", run) == pytest.approx(24.04, abs=0.01)
    assert _read("scan_flops_share.conv_moe", run) == pytest.approx(3.22, abs=0.01)
    assert _read("expert_tile_fill", run) == pytest.approx(12.75, abs=0.01)
    assert _read("experts_touched_share", run) == pytest.approx(99.996, abs=0.001)
    assert _read("conv_rows_per_iteration", run) == pytest.approx(126.49, abs=0.01)
    assert _read("expert_rows_per_iteration.conv_moe", run) == pytest.approx(16.31, abs=0.01)


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_state_a_slot_gives_nothing(cfg, metric):
    """The parent commit's engine has no such counts, and a model without conv
    layers has nothing to count: the line leaves the metric out."""
    assert callable(loader.load_module("layer_metrics", metric).read)
    assert _read(metric, _run(cfg, _trace(counts=False))) is None
    assert _read(metric, _run(cfg, None, trace=None)) is None
    assert _read(metric, {}) is None


def test_the_older_expert_reader_cannot_read_this_family(cfg):
    """Why ``expert_rows_per_iteration.conv_moe`` exists: the accepted reader
    asks the configuration for ``first_k_dense_replace`` / ``n_routed_experts``,
    keys this family's published config does not have."""
    with pytest.raises(KeyError):
        _read("expert_rows_per_iteration", _run(cfg, _trace()))


# ------------------------------------------------ a tiny cell, end to end
def _measure(tmp_path, *, control=0, seconds=1.5, seed=2**31 + 38):
    from benchmark.harness.compile_meter import CompileMeter
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cell = loader.load_cell("tiny.conv-moe.chat", root=FIXTURE)
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
    """``--control 1``: the W8A8 reference decides, and the one that makes the
    MECHANISM wrong (the convolution's current tap alone: a state that reads
    zero every iteration) is read beside it: each lies over both limits."""
    out = _measure(tmp_path, control=1, seconds=3.0)
    assert out["correct"] is False and out["attempted"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{") and '"gaps"' in l]
    gaps = {n["gaps"]: n for n in notes}
    assert set(gaps) == {"served", "int8", "taps1"}
    limits = loader.load_cell("tiny.conv-moe.chat", root=FIXTURE).config["check"]["limits"]
    assert gaps["served"]["max"] < limits["max_gap_nats"]
    for low in ("int8", "taps1"):
        assert gaps[low]["mean"] > limits["mean_gap_nats"], low
        assert gaps[low]["max"] > limits["max_gap_nats"], low
    assert gaps["taps1"]["mean"] > gaps["int8"]["mean"]


# ------------------------------------------------------- names and numbers
def test_the_cell_its_files_and_its_traffic_are_as_the_issue_names_them(cfg):
    bench = loader.load_benchmark()
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (CONFIG, "chat-batch", 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert sum(c["name"] == CONFIG for c in bench["configs"]) == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1      # no second cell
    assert entry["reduced"] == REDUCED == list(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"]
    assert (cfg["family"], cfg["path"], cfg["chips"]) == ("conv_gqa_moe", "serve", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, work))
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_completions"], t["first_wave"]) == (
        "closed_loop", 256, 32, 0.05)
    assert t["sizes"]["count"] == 64 and t["sizes"]["seed"] == 20260938
    assert t["sampling"] == {"temperature": 0.0}
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 256, "sigma": 0.8,
                                    "min": 32, "max": 2048}
    assert t["sizes"]["new_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                                        "min": 32, "max": 768}
    seeds = {loader.load_cell(w["name"]).traffic.get("sizes", {}).get("seed")
             for w in bench["workloads"] if w["name"] != CELL}
    assert t["sizes"]["seed"] not in seeds                       # a seed of its own
    e = cfg["engine"]
    assert e == {"max_batch_size": 128, "max_seq_len": 2816, "block_size": 64,
                 "token_budget": 512, "num_blocks": 2048, "megastep_k": 8}
    assert t["clients"] == 2 * e["max_batch_size"]               # a prompt always waits
    assert t["sizes"]["prompt"]["max"] + t["sizes"]["new_tokens"]["max"] <= e["max_seq_len"]
    assert cfg["check"]["pad_to"] == e["max_seq_len"]
    assert cfg["control"] == dict(cfg["control"], reference_precision="int8",
                                  also_read=["taps1", "bf16"])
    ouro = {m["name"] for m in loader.load_cell("ouro2.6b.serve.reason-batch").per_layer}
    assert {m["name"] for m in cell.per_layer} == ouro == ELEVEN
    assert "scan_hbm_share" not in ouro
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # the family's own six readers are files; BENCHMARK.json cannot list them
    # yet (three tests pin the list's length: PERF.md section 7)
    listed = {m["name"] for m in bench["per_layer"]}
    for metric in READERS:
        assert callable(loader.load_module("layer_metrics", metric).read)
        assert metric not in listed
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


def test_the_cell_of_pr_32_is_still_as_its_issue_gave_it():
    """Every assertion of ``test_dsa_cell.py``'s test of the same purpose but
    its pins of last place (``configs[-1]``, ``workloads[-1]``) and of length
    (6 cells), which this PR's configuration, appended as the driver asks,
    turns red."""
    bench = loader.load_benchmark()
    name, config = "deepseekv32.serve.longdoc-batch", "deepseek-v3.2-exp.serve1"
    cell = loader.load_cell(name)
    dsa = cell.config
    assert (cell.config_name, cell.traffic_name, cell.chips) == (config, "longdoc-batch", 1)
    names = [c["name"] for c in bench["configs"]]
    assert names.index(config) == names.index(CONFIG) - 1        # this PR's stands behind it
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(name) == cells.index(CELL) - 1
    entry = bench["configs"][names.index(config)]
    assert entry["reduced"] == list(dsa["reduced"]) == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert entry["file"] == f"benchmark/configs/{config}.json"
    assert entry["source"] == dsa["source"]
    assert (dsa["family"], dsa["path"], dsa["chips"]) == ("mla_dsa_moe", "serve", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, bench["workloads"][cells.index(name)]))
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_completions"], t["first_wave"]) == (
        "closed_loop", 32, 8, 0.05)
    assert t["sizes"]["count"] == 32 and t["sampling"] == {"temperature": 0.0}
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 8192, "sigma": 0.5,
                                    "min": 4096, "max": 16384}
    assert t["sizes"]["new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.5,
                                        "min": 64, "max": 768}
    seeds = {loader.load_cell(w["name"]).traffic.get("sizes", {}).get("seed")
             for w in bench["workloads"] if w["name"] != name}
    assert t["sizes"]["seed"] not in seeds
    e = dsa["engine"]
    assert e == {"max_batch_size": 24, "max_seq_len": 17152, "block_size": 64,
                 "token_budget": 512, "num_blocks": 5120, "megastep_k": 8}
    assert t["sizes"]["prompt"]["max"] + t["sizes"]["new_tokens"]["max"] <= e["max_seq_len"]
    assert t["sizes"]["prompt"]["min"] > dsa["index_topk"]
    assert dsa["check"]["pad_to"] == e["max_seq_len"]
    assert dsa["control"] == dict(dsa["control"], reference_precision="int8",
                                  also_read=["recent", "dense", "bf16"])
    assert {m["name"] for m in cell.per_layer} == ELEVEN
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    for metric in ("dsa_selected_share", "dsa_read_per_selected", "scan_hbm_share.dsa",
                   "scan_flops_share.dsa", "expert_rows_per_iteration"):
        assert callable(loader.load_module("layer_metrics", metric).read)


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    return next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "LFM2-24B-A2B")


def test_published_is_the_catalogs_row_key_by_key(cfg):
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == row["config"]
    assert len(cfg["assumed"]) >= 8 and "four-stage pipeline" in cfg["stands_for"]
    for key in ("tie_word_embeddings", "head_dim", "expert_bias", "rope"):
        assert key in cfg["assumed"], key


@pytest.mark.parametrize("key", sorted(json.loads(open(
    os.path.join(loader.ROOT, "benchmark", "configs", CONFIG + ".json")).read())["published"]))
def test_every_key_outside_reduced_is_as_published(cfg, key):
    if key in REDUCED:
        assert cfg[key] != cfg["published"][key] and key in cfg["reduced"]
    else:
        assert cfg[key] == cfg["published"][key], key


def test_the_cut_keeps_every_width_and_the_guides_floors(cfg):
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "conv_L_cache",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "vocab_size", "num_dense_layers", "layer_types"):
        assert key not in REDUCED and cfg[key] == cfg["published"][key]
    assert "experts_held" not in cfg and cfg["num_experts"] == 64 >= 8      # every expert
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]) == (10, 40)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    # both leading dense layers, then two WHOLE periods of the published pattern
    assert kinds == ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]


def test_the_configuration_builds_the_programs_model(cfg):
    import math

    family = loader.load_module("families", cfg["family"])
    mc = family.model_config(cfg)
    assert (mc.num_experts, mc.experts_held, mc.head_dim) == (64, (0, 64), 64)
    assert mc.layers_of("full_attention") == [2, 6] and len(mc.layers_of("conv")) == 8
    assert mc.tie_word_embeddings and mc.dtype == "bfloat16"
    conv, attn, dense, sparse, outer = family.leaf_shapes(cfg)
    count = lambda *shapes: sum(math.prod(s) for d in shapes for s in d.values())  # noqa: E731
    total = (2 * count(conv, dense) + 2 * count(attn, sparse) + 6 * count(conv, sparse)
             + count(outer))
    assert total == cost.parameters(cfg)["total"]
