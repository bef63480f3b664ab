"""The ``mla_dsa_moe`` family's part of the benchmark on the CPU: the parameter
and byte arithmetic of ISSUE 32 on the published shapes, its four readers on
a small trace written out by hand, a tiny cell of it end to end through
benchmark.run's functions, sound and under the controls, and the cell, its
files and its traffic as the issue names them."""
import argparse
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import loader
from benchmark.harness import mla_dsa_cost as cost
from benchmark.harness import mla_moe_cost
from benchmark.harness.program_trace import ProgramTrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_dsa")
CELL = "deepseekv32.serve.longdoc-batch"
CONFIG = "deepseek-v3.2-exp.serve1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]
READERS = ("dsa_selected_share", "dsa_read_per_selected", "scan_hbm_share.dsa",
           "scan_flops_share.dsa")


@pytest.fixture(scope="module")
def cfg():
    return loader.load_cell(CELL).config


# ------------------------------------------------------------------- shapes
def test_parameters_are_the_issues_arithmetic(cfg):
    assert mla_moe_cost.attention_params(cfg) == 187_105_280          # MLA, a layer
    assert cost.index_params(cfg) == 13_959_168                       # + 256 of its LayerNorm
    assert mla_moe_cost.expert_params(cfg) == 44_040_192              # routed or shared
    assert mla_moe_cost.layer_counts(cfg) == (1, 4) and mla_moe_cost.router_outputs(cfg) == 256
    dense_ffn = 3 * 7168 * 18432
    assert dense_ffn == 396_361_728
    assert cost.trunk_params(cfg) == (5 * (187_105_280 + 13_959_168) + dense_ffn
                                      + 4 * (7168 * 256 + 44_040_192))
    # with the 16 held experts a layer, the embedding and the head: 4.635 B
    total = (cost.trunk_params(cfg) + 4 * 16 * 44_040_192
             + 2 * mla_moe_cost.head_params(cfg))
    assert abs(total / 4.635e9 - 1) < 0.001
    assert cost.entry_values(cfg) == 512 + 64 + 128
    e = cfg["engine"]
    # the pool as it is stored: the latent at 640 values, index_k at 128
    assert e["num_blocks"] * e["block_size"] == 327_680
    assert 327_680 * (640 + 128) * 2 * 5 == 2_516_582_400


def test_an_iterations_bytes_and_a_launchs_flops(cfg):
    fixed = 2 * (cost.trunk_params(cfg) + mla_moe_cost.head_params(cfg))
    assert cost.iteration_bytes(cfg, 0, 0, 0) == fixed
    # 1,000 live positions: their index keys; 300 selected: their latent entries
    assert cost.iteration_bytes(cfg, 0, 1000, 300) - fixed == 5 * 2 * (1000 * 128 + 300 * 576)
    # the queries of a row share what they bring: no more entries than are live
    assert (cost.iteration_bytes(cfg, 0, 1000, 5000) - fixed
            == 5 * 2 * (1000 * 128 + 1000 * 576))
    assert (cost.iteration_bytes(cfg, 10_000, 0, 0) - fixed
            == pytest.approx(4 * 16 * 88_080_384 + 10_000 * 704 * 2 * 5))
    assert cost.launch_flops(cfg, 1, 0, 0, 0, 0) == 2.0 * cost.trunk_params(cfg)
    assert (cost.launch_flops(cfg, 0, 3, 2, 7, 11)
            == 2.0 * 44_040_192 * 3 + 2.0 * mla_moe_cost.head_params(cfg) * 2
            + 5 * (2 * 64 * 128 * 7 + 2 * 128 * 1088 * 11))


# ------------------------------------------------------------------ readers
class _Cell:
    name = "no.such.cell"


def _trace(counts=True):
    """Three launches inside a 10 us window (a fourth starts before it): a
    mixed scan of 8 iterations, a decode-only one of 4, and a prefill step."""
    def harvest(tokens, picks, queries, scored):
        h = {"moe_tokens": 4 * tokens, "moe_local_picks": picks}
        if counts:
            h.update(dsa_queries=queries, dsa_positions_scored=scored,
                     dsa_positions_selected=2048 * queries, dsa_positions_read=scored,
                     attn_positions_live=scored // 10)
        return h

    host = [("engine.harvest", 100, 50, harvest(1, 1, 1, 3000)),       # its launch is outside
            ("engine.launch", 900, 50, {"kind": "mixed", "k": 8, "launch": 1, "passes": 1}),
            ("engine.harvest", 4100, 100, harvest(4000, 900, 3000, 24_000_000)),
            ("engine.launch", 4900, 50, {"kind": "mega", "k": 4, "launch": 2, "passes": 1}),
            ("engine.harvest", 8100, 100, harvest(96, 30, 96, 768_000)),
            ("engine.launch", 8900, 50, {"kind": "step", "k": 1, "launch": 3, "passes": 1}),
            ("engine.harvest", 9600, 100, harvest(500, 100, 0, 0))]
    modules = [("jit_mixed", -2000, 2500), ("jit_mixed", 1000, 3000), ("jit_mega", 5000, 3000),
               ("jit_step", 9000, 500)]
    return ProgramTrace(window=(0, 10_000), host=host, modules=modules, ops=[])


def _run(cfg, program_trace, **kw):
    return dict({"trace": object(), "cell": _Cell(), "program_trace": program_trace,
                 "config": cfg, "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                 "counters": {"megasteps": 10, "megastep_tokens": 400},
                 "live_tokens_mean": 200_000.0}, **kw)


def _read(metric, run):
    return loader.load_module("layer_metrics", metric).read(run)


def test_scan_sums_read_the_scans_harvest_spans(cfg):
    sums = cost.scan_sums(_run(cfg, _trace()))
    assert sums == {"launches": 2, "k": 12, "seconds": 6e-6, "dsa_queries": 3096,
                    "dsa_positions_scored": 24_768_000,
                    "dsa_positions_selected": 2048 * 3096,
                    "dsa_positions_read": 24_768_000, "attn_positions_live": 2_476_800,
                    "moe_tokens": 4 * 4096,
                    "moe_local_picks": 930}


def test_the_four_readers_on_a_trace_written_by_hand(cfg):
    run = _run(cfg, _trace())
    assert _read("dsa_selected_share", run) == pytest.approx(100 * 2048 * 3096 / 24_768_000)
    assert _read("dsa_read_per_selected", run) == pytest.approx(24_768_000 / (2048 * 3096))
    # the live contexts are the launches' own, not the window's ticks'
    # (``live_tokens_mean`` counts the decoding rows alone)
    nbytes = cost.iteration_bytes(cfg, 4096 / 12, 2_476_800 / 12, 2048 * 3096 / 12)
    assert _read("scan_hbm_share.dsa", run) == pytest.approx(
        100 * nbytes / 819e9 / (6e-6 / 12))
    flops = cost.launch_flops(cfg, 4096, 930, 2 * 40, 24_768_000, 2048 * 3096)
    assert _read("scan_flops_share.dsa", run) == pytest.approx(100 * flops / (197e12 * 6e-6))
    # the expert layer's reader, a file of PR 26, reads this cell as it stands
    # (by its own rule: every harvest span inside the window, the scans' iterations)
    assert _read("expert_rows_per_iteration", run) == pytest.approx(
        (1 + 900 + 30 + 100) / 4 / (4 * 16 * 6))


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_selections_counts_gives_nothing(cfg, metric):
    """The parent commit's engine has no such counts, and a model without an
    indexer has nothing to count: the line leaves the metric out."""
    assert _read(metric, _run(cfg, _trace(counts=False))) is None
    assert _read(metric, _run(cfg, None, trace=None)) is None
    assert _read(metric, {}) is None


# ------------------------------------------------ a tiny cell, end to end
def _measure(tmp_path, *, control=0, seconds=1.5, seed=2**31 + 32):
    from benchmark.harness.compile_meter import CompileMeter
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cell = loader.load_cell("tiny.dsa.docs", root=FIXTURE)
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
    """``--control 1``: the W8A8 reference decides, and the two that make the
    MECHANISM wrong (the most recent positions, every position) are read
    beside it: each lies over both limits."""
    out = _measure(tmp_path, control=1, seconds=3.0)
    assert out["correct"] is False and out["attempted"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{") and '"gaps"' in l]
    gaps = {n["gaps"]: n for n in notes}
    assert set(gaps) == {"served", "int8", "recent", "dense"}
    limits = loader.load_cell("tiny.dsa.docs", root=FIXTURE).config["check"]["limits"]
    assert gaps["served"]["max"] < limits["max_gap_nats"]
    for low in ("int8", "recent", "dense"):
        assert gaps[low]["mean"] > limits["mean_gap_nats"], low
        assert gaps[low]["max"] > limits["max_gap_nats"], low


# ------------------------------------------------------- names and numbers
def test_the_cell_its_files_and_its_traffic_are_as_the_issue_names_them(cfg):
    bench = loader.load_benchmark()
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (CONFIG, "longdoc-batch", 1)
    assert bench["configs"][-1]["name"] == CONFIG          # a new configuration stands LAST
    assert bench["workloads"][-1]["name"] == CELL
    entry = bench["configs"][-1]
    assert entry["reduced"] == REDUCED == list(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"]
    assert (cfg["family"], cfg["path"], cfg["chips"]) == ("mla_dsa_moe", "serve", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, bench["workloads"][-1]))
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_completions"], t["first_wave"]) == (
        "closed_loop", 32, 8, 0.05)
    assert t["sizes"]["count"] == 32 and t["sampling"] == {"temperature": 0.0}
    assert t["sizes"]["prompt"] == {"dist": "lognormal", "median": 8192, "sigma": 0.5,
                                    "min": 4096, "max": 16384}
    assert t["sizes"]["new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.5,
                                        "min": 64, "max": 768}
    seeds = {loader.load_cell(w["name"]).traffic.get("sizes", {}).get("seed")
             for w in bench["workloads"] if w["name"] != CELL}
    assert t["sizes"]["seed"] not in seeds                       # a seed of its own
    e = cfg["engine"]
    assert e == {"max_batch_size": 24, "max_seq_len": 17152, "block_size": 64,
                 "token_budget": 512, "num_blocks": 5120, "megastep_k": 8}
    assert t["sizes"]["prompt"]["max"] + t["sizes"]["new_tokens"]["max"] <= e["max_seq_len"]
    assert t["sizes"]["prompt"]["min"] > cfg["index_topk"]       # every prompt is selected over
    assert cfg["check"]["pad_to"] == e["max_seq_len"]
    assert cfg["control"] == dict(cfg["control"], reference_precision="int8",
                                  also_read=["recent", "dense", "bf16"])
    ouro = {m["name"] for m in loader.load_cell("ouro2.6b.serve.reason-batch").per_layer}
    assert {m["name"] for m in cell.per_layer} == ouro and len(ouro) == 11
    assert "scan_hbm_share" not in ouro
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # the family's own four readers are files; BENCHMARK.json cannot list them
    # yet (tests/benchmark/test_program_trace.py pins the list: PERF.md section 7)
    for metric in READERS + ("expert_rows_per_iteration",):
        assert callable(loader.load_module("layer_metrics", metric).read)
    assert len(bench["per_layer"]) == 19 and len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    return next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "DeepSeek-V3.2-Exp")


def test_published_is_the_catalogs_row_key_by_key(cfg):
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == row["config"]
    assert len(cfg["assumed"]) >= 10 and "16 chips" in cfg["stands_for"]


@pytest.mark.parametrize("key", sorted(json.loads(open(
    os.path.join(loader.ROOT, "benchmark", "configs", CONFIG + ".json")).read())["published"]))
def test_every_key_outside_reduced_is_as_published(cfg, key):
    if key in REDUCED:
        assert cfg[key] != cfg["published"][key] and key in cfg["reduced"]
    else:
        assert cfg[key] == cfg["published"][key], key


def test_the_cut_keeps_every_width_and_the_guides_floors(cfg):
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "index_head_dim", "index_n_heads", "index_topk", "num_attention_heads",
                "num_experts_per_tok", "n_group", "topk_group"):
        assert key not in REDUCED and cfg[key] == cfg["published"][key]
    assert cfg["router_outputs"] == cfg["published"]["n_routed_experts"] == 256
    assert cfg["experts_held"] == [0, 16] and cfg["n_routed_experts"] == 16 >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    mem = cfg["memory"]
    fullest = max(v["live"] for v in mem["compiled_for_v5e"].values())
    assert 0.25 * mem["bytes_limit"] < fullest < mem["bytes_limit"] - 1.5e9


def test_the_configuration_builds_the_programs_model(cfg):
    family = loader.load_module("families", cfg["family"])
    mc = family.model_config(cfg)
    assert (mc.n_routed_experts, mc.experts_held, mc.index_topk) == (256, (0, 16), 2048)
    assert mc.latent_cache_width == 640 and abs(mc.mscale - 1.3689) < 1e-4
    dense, sparse, outer = family.leaf_shapes(cfg)
    count = lambda shapes: sum(int(__import__("math").prod(s)) for s in shapes.values())  # noqa: E731
    total = count(dense) + 4 * count(sparse) + count(outer)
    assert abs(total / 4.635e9 - 1) < 0.001
