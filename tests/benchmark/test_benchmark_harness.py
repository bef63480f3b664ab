"""The benchmark's own tests, on the CPU: the data files load, the generators
and the arithmetic are what they say, the plain reference agrees with the
program at a tiny size, a tiny cell of each path runs end to end through
benchmark.run's functions, the controls and a broken timed path come out as
not correct, and off a TPU the command fails without a result."""
import argparse
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import flops, loader, report, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = loader.load_benchmark()


# ------------------------------------------------------------ the data files
def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for kind in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[kind]}) == len(BENCH[kind])
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    assert os.path.getsize(os.path.join(loader.ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("cell_name", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_and_names_files_that_exist(cell_name):
    cell = loader.load_cell(cell_name)
    cfg = cell.config
    for key in ("source", "reduced", "assumed", "stands_for", "family", "path",
                "check", "control"):
        assert key in cfg, f"{cell.config_name} lacks {key}"
    entry = next(c for c in BENCH["configs"] if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for kind, name in (("drivers", cfg["path"]), ("families", cfg["family"]),
                       ("references", cfg["family"]),
                       ("generators", cell.traffic["generator"])):
        assert cell.module(kind, name)
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    for m in cell.end_to_end:
        assert callable(cell.module("end_to_end", m["name"]).read)
    for m in cell.per_layer:
        assert callable(cell.module("layer_metrics", m["name"]).read)
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert "workloads" not in moved or cell_name in moved["workloads"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_a_reader_that_finds_nothing_to_read_returns_nothing(metric):
    read = loader.load_module("layer_metrics", metric).read
    assert read({}) is None and read({"trace": None, "counters": {}}) is None


WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*)_size|^d_|_dim$|_rank$|head_size"
                   r"|expan|experts_per_tok")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_keeps_what_its_source_published(entry):
    """Held against the file's own ``published`` (its source's values, as the
    source names them): a key that is not listed in ``reduced`` is as
    published, one that is listed differs, and no width is ever listed."""
    cfg = json.load(open(os.path.join(loader.ROOT, entry["file"])))
    published = cfg["published"]
    assert published and cfg["source"].startswith(entry["source"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert not [k for k in entry["reduced"] if WIDTH.search(k)]
    for key, value in published.items():
        if key in entry["reduced"]:
            assert cfg[key] != value, f"{key} is listed as reduced and is as published"
        else:
            assert cfg[key] == value, f"{key}: {cfg[key]!r} runs, {value!r} is published"


def test_fixture_tree_adds_a_cell_a_configuration_and_a_metric_as_files():
    cell = loader.load_cell("tiny.serve.batch", root=FIXTURE)
    assert cell.config["hidden_size"] == 128 and cell.traffic["clients"] == 6
    # the metric lives in the fixture tree only; the driver in the repository's
    assert cell.module("layer_metrics", "fixture_requests_counted").read({"counted": [1, 2]}) == 2
    assert cell.module("drivers", "serve").__name__.endswith("serve")
    with pytest.raises(FileNotFoundError):
        cell.module("generators", "no_such_generator")
    with pytest.raises(KeyError):
        loader.load_cell("no.such.cell")


# ----------------------------------------------------------------- generators
def _traffic(name):
    return json.load(open(os.path.join(loader.ROOT, loader.traffic_path(name))))


def _serve_mixes():
    names = {w["traffic"] for w in BENCH["workloads"]}
    return sorted(n for n in names if "sizes" in _traffic(n))


@pytest.mark.parametrize("mix", _serve_mixes())
def test_sizes_are_one_fixed_set_within_their_clips(mix):
    t = _traffic(mix)
    a, b = traffic.sizes(t, 300), traffic.sizes(t, 300)
    assert a == b
    p, n = t["sizes"]["prompt"], t["sizes"]["new_tokens"]
    assert all(p["min"] <= x <= p["max"] and n["min"] <= y <= n["max"] for x, y in a)
    assert min(x for x, _ in a) == p["min"] or max(x for x, _ in a) == p["max"]
    assert abs(np.median([x for x, _ in a]) / p["median"] - 1) < 0.2


def test_token_stream_is_seeded_and_rows_differ():
    gen = loader.load_module("generators", "token_stream")
    t = _traffic("pretrain-2k")
    a, b = gen.batches(t, 5, 32768), gen.batches(t, 5, 32768)
    x0, x1, y0 = next(a), next(a), next(b)
    assert x0.shape == (t["batch"], t["seq"]) and x0.dtype == np.int32
    assert (x0 == y0).all() and not (x0 == x1).all()
    assert len({row.tobytes() for row in x0}) == t["batch"]
    assert not (next(gen.batches(t, 6, 32768)) == x0).all()


class _StalledFrontend:
    """A fake system on a fake clock: every step takes ``step_s``; a request
    emits one token a step from the step after it was submitted."""

    def __init__(self, step_s):
        self.now, self.step_s = 0.0, step_s
        self.live, self.ended, self.n = {}, [], 0

    def clock(self):
        return self.now

    def submit(self, prompt, max_new, on_token):
        self.n += 1
        self.live[self.n] = [max_new, on_token]
        return self.n

    @property
    def pending(self):
        return len(self.live)

    def step(self):
        self.now += self.step_s
        for rid, st in list(self.live.items()):
            st[1](rid, 1)
            st[0] -= 1
            if st[0] == 0:
                del self.live[rid]
                self.ended.append((rid, True))

    def poll(self):
        out, self.ended = self.ended, []
        return out

    def slots_free(self):
        return 0


class _NoHooks:
    def on_open(self): pass
    def on_tick(self, rel, log): pass
    def on_close(self): pass


def test_closed_loop_sends_one_fixed_sequence_whatever_the_seed():
    cl = loader.load_module("generators", "closed_loop")
    t = dict(_traffic("batch"), clients=4, ramp_completions=2)
    runs = []
    for seed in (5, 5, 2**31 + 9):
        fe = _StalledFrontend(step_s=0.25)
        win = cl.drive(fe, t, seed, 30.0, 1000, fe.clock, _NoHooks())
        runs.append([(len(r.prompt), r.max_new, tuple(r.prompt[:3])) for r in win["requests"]])
        assert win["t_close"] - win["t_open"] == pytest.approx(30.0, abs=0.26)
        assert all(r.done_at >= win["t_open"] for r in win["counted"])
        assert sum(len(r.stamps) for r in win["counted"]) > 0
    assert runs[0] == runs[1] and runs[0] != runs[2]
    # the seed draws the token ids and nothing of the work: the same sizes in the same order
    assert [x[:2] for x in runs[0]] == [x[:2] for x in runs[2]]
    pool = traffic.sizes(t, t["sizes"]["count"])
    sent = runs[0]                      # past the shortened first wave: the pool, in order
    assert [(p, n) for p, n, _ in sent[4:4 + len(pool)]] == [
        pool[(4 + i) % len(pool)] for i in range(len(sent[4:4 + len(pool)]))]
    assert [p for p, _, _ in sent[:4]] == [p for p, _ in pool[:4]]
    assert all(1 <= n <= pool[i][1] for i, (_, n, _) in enumerate(sent[:4]))


# ------------------------------------------------------- the trace reduction
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return xplane.Trace.from_dict(json.load(f))


def test_trace_busy_idle_modules_and_operations(recorded):
    assert xplane.window_ns(recorded) == (0, 10000)
    busy = xplane.busy_by_device(recorded)
    assert busy["/device:TPU:0"] == pytest.approx(7000e-9)   # [1000,5000) + [6000,9000)
    assert busy["/device:TPU:1"] == pytest.approx(8000e-9)
    mean_busy, window = xplane.busy_and_window(recorded)
    assert (mean_busy, window) == (pytest.approx(7500e-9), pytest.approx(10000e-9))
    assert xplane.module_durations(recorded, ("jit_step",)) == [4000e-9, 3000e-9]
    assert xplane.op_seconds(recorded, xplane.is_custom_call) == pytest.approx(1000e-9)
    top = dict(xplane.top_ops(recorded))
    assert top["fusion.1"] == pytest.approx(5000e-9) and list(top)[0] == "fusion.1"
    gaps = dict(xplane.idle_gaps(recorded))
    # idle: [0,1000) under bench.step, [5000,6000) under bench.loss_read, [9000,10000) bare
    assert gaps == {"bench.step": pytest.approx(1000e-9),
                    "bench.loss_read": pytest.approx(1000e-9),
                    "(no span)": pytest.approx(1000e-9)}
    run = {"trace": recorded, "chips": 2}
    assert loader.load_module("layer_metrics", "device_idle_share.train").read(run) == pytest.approx(25.0)
    assert loader.load_module("layer_metrics", "step_device_ms").read(run) == pytest.approx(3.5e-3)
    assert loader.load_module("layer_metrics", "pallas_time_share").read(run) == pytest.approx(100 / 7)
    assert loader.load_module("layer_metrics", "step_device_ms").read({"trace": None}) is None


def test_an_operations_event_name_is_cut_to_its_hlo_name():
    text = "%fusion.12 = (f32[4]{0}, f32[4]{0}) fusion(f32[4]{0} %p), kind=kLoop"
    assert xplane.short_name(text) == "fusion.12"
    kernel = "%checkpoint.5 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x)"
    assert xplane.short_name(kernel) == "custom-call:checkpoint.5"
    assert xplane.is_custom_call(xplane.short_name(kernel))
    assert xplane.short_name("jit_step(123)") == "jit_step(123)"


# ------------------------------------------------------------------ flops.py
def test_flops_on_hand_worked_shapes():
    cfg = {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768,
           "num_hidden_layers": 2}
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert flops.layer_matmul_params(cfg) == layer == 218_103_808
    assert flops.matmul_params(cfg) == 2 * layer + 4096 * 32768
    assert flops.total_params(cfg) == 2 * layer + 2 * 4096 * 32768 + 5 * 4096 == 704_663_552
    # attention forward at S=2048: 4 * 32 heads * 2048^2 * 128 / 2 per sequence
    assert flops.attention_flops_fwd(cfg, 1, 2048) == 4 * 32 * 2048 * 2048 * 128 / 2
    per_token = 6 * flops.matmul_params(cfg) + 3 * 2 * (4 * 32 * 2048 * 128 / 2)
    assert flops.train_flops_per_token(cfg, 2048) == pytest.approx(per_token)
    assert flops.kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2 * 2
    assert flops.decode_bytes_per_iteration(cfg, 1000) == flops.matmul_params(cfg) * 2 + 1000 * 8192


def test_percentile_and_checks():
    assert report.percentile([1, 2, 3, 4, 5], 50) == 3
    assert report.percentile(list(range(101)), 95) == 95
    c = report.Checks()
    assert not c.correct                       # nothing compared is not correct
    c.add("a", 0.5, 1.0)
    c.add("n", 3, 1, at_least=True)
    assert c.correct
    c.add("nan", float("nan"), 1.0)
    assert not c.correct


# --------------------------------------- the reference against the program
@pytest.fixture(scope="module")
def tiny():
    cell = loader.load_cell("tiny.serve.batch", root=FIXTURE)
    family = cell.module("families", "llama_dense")
    reference = cell.module("references", "llama_dense")
    return cell.config, family, reference


def test_reference_forward_agrees_with_the_programs_model(tiny):
    import paddle_tpu as P
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cfg, family, reference = tiny
    weights = family.make_weights(cfg, 11)
    model = family.build_model(cfg)
    family.assign(model, weights)
    model.eval()
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], 48)
    want = np.asarray(model(P.to_tensor(ids[None].astype(np.int32)))._value[0])
    got = np.asarray(reference.logits_at(weights, cfg, ids, np.arange(48)))
    assert np.abs(got - want).max() < 2e-4
    # padding behind the rows that are read changes nothing (causal)
    padded = np.concatenate([ids, np.zeros(16, ids.dtype)])
    again = np.asarray(reference.logits_at(weights, cfg, padded, np.arange(48)))
    assert np.abs(again - got).max() < 1e-5
    # and the lower precision of the control moves them
    low = np.asarray(reference.logits_at(weights, cfg, ids, np.arange(48), quant="int8"))
    assert 1e-3 < np.abs(low - got).max() < 1.0
    # an int8 cache moves only what is decoded: the prompt's rows, the last of
    # which gives the first new token, are attended before they are rounded
    kv = np.asarray(reference.logits_at(weights, cfg, ids, np.arange(48), quant="int8-kv",
                                        n_prompt=30))
    assert np.abs(kv[:30] - got[:30]).max() < 1e-5
    assert 1e-4 < np.abs(kv[30:] - got[30:]).max() < np.abs(low - got).max()
    with pytest.raises(ValueError):
        reference.logits_at(weights, cfg, ids, np.arange(48), quant="int3")


def test_weights_are_seeded_and_take_a_seed_past_int32(tiny):
    cfg, family, _ = tiny
    import jax

    a, b = family.make_weights(cfg, 2**31 + 3), family.make_weights(cfg, 2**31 + 3)
    c = family.make_weights(cfg, 3)
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not all((x == y).all() for x, y in zip(la, lc))
    assert len(la) == 3 + 9 * cfg["num_hidden_layers"]
    wq = a["layers"][0]["wq"]
    assert abs(float(wq.std()) * cfg["hidden_size"] ** 0.5 - 1) < 0.1


def test_score_judges_the_served_tokens_or_the_controls_first_precision(tiny):
    cfg, family, reference = tiny
    serve = loader.load_module("drivers", "serve")
    weights = family.make_weights(cfg, 5)
    req = traffic.Request(0, 0.0, np.random.default_rng(1).integers(1, 512, 24).tolist(), 96)
    for _ in range(96):                               # serve what the reference puts first
        ids = np.zeros(128, np.int32)
        ids[:24 + len(req.tokens)] = req.prompt + req.tokens
        row = [23 + len(req.tokens)]
        req.tokens.append(int(np.asarray(reference.logits_at(weights, cfg, ids, row)).argmax()))
    cfg = dict(cfg, check=dict(cfg["check"], pad_to=128))
    sound, control = report.Checks(), report.Checks()
    serve.score(reference, weights, cfg, [req], sound)
    rows = {r["check"]: r for r in sound.rows}
    assert sound.correct and rows["max_gap_nats"]["value"] == rows["mean_gap_nats"]["value"] == 0
    serve.score(reference, weights, cfg, [req], control, control=True)
    rows = {r["check"]: r for r in control.rows}
    assert not control.correct and rows["max_gap_nats"]["value"] > rows["mean_gap_nats"]["value"] > 0
    nothing = report.Checks()
    serve.score(reference, weights, cfg, [], nothing)      # nothing finished: not correct
    assert not nothing.correct


# ------------------------------------------- whole runs at a tiny size (CPU)
def _measure(cell_name, tmp_path, *, trace=0, control=0, seconds=1.5, seed=2**31 + 17):
    from benchmark.harness.compile_meter import CompileMeter
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    cell = loader.load_cell(cell_name, root=FIXTURE)
    args = argparse.Namespace(workload=cell_name, seed=seed, seconds=seconds,
                              trace=trace, control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}   # the chip look is skipped
    line = bench_run.measure(cell, args, device, CompileMeter(), str(tmp_path))
    out = json.loads(line)
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One sound run of each fixture cell, shared by the tests below."""
    tmp = tmp_path_factory.mktemp("bench")
    return {name: _measure(name, tmp / name) for name in
            ("tiny.serve.batch", "tiny.train")}


@pytest.mark.parametrize("cell_name,metrics", [
    ("tiny.serve.batch", {"serve_tokens_per_s", "setup_s"}),
    ("tiny.train", {"train_tokens_per_s", "setup_s"})])
def test_a_tiny_cell_runs_end_to_end_and_is_correct(runs, cell_name, metrics):
    out = runs[cell_name]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(m["value"] > 0 and math.isfinite(m["value"]) for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"


def test_a_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch, recorded):
    # the profiler has no device plane on the CPU: the recorded trace stands in
    monkeypatch.setattr(xplane, "start", lambda d: None)
    monkeypatch.setattr(xplane, "stop", lambda: None)
    monkeypatch.setattr(xplane, "load", lambda d: recorded)
    out = _measure("tiny.serve.batch", tmp_path, trace=1)
    assert set(out["metrics"]) == {"fixture_requests_counted", "host_share.batch",
                                   "tokens_per_launch", "kv_pool_live_share"}
    assert 0 < out["metrics"]["kv_pool_live_share"]["value"] <= 100
    assert 0 < out["metrics"]["host_share.batch"]["value"] < 100
    assert 1 <= out["metrics"]["tokens_per_launch"]["value"] <= 4 * 4
    assert out["device"]["busy_s"] == pytest.approx(7500e-9)
    assert out["device"]["window_s"] == pytest.approx(10000e-9)
    assert out["breakdown"]["device_ops"][0][0] == "fusion.1"
    assert len(out["breakdown"]["idle_gaps"]) <= 10


# ------------------------------------------------ controls and broken paths
def test_control_serving_in_int8_is_not_correct(tmp_path):
    # a window long enough to finish the 16 requests the fixture samples: at this
    # size int8 moves one token in thirty, and a few dozen tokens can all agree
    out = _measure("tiny.serve.batch", tmp_path, control=1, seconds=3.0)
    assert out["correct"] is False


def test_control_training_with_bf16_optimizer_state_is_not_correct(tmp_path):
    sound = _measure("tiny.train-bf16", tmp_path / "sound")
    assert sound["correct"] is True
    out = _measure("tiny.train-bf16", tmp_path / "control", control=1)
    assert out["correct"] is False and out["metrics"] == {}


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path, monkeypatch):
    from paddle_tpu.inference import ServingEngine

    real = ServingEngine.step

    def step(self):
        emitted = real(self)
        return {rid: [(t + 1) % 512 for t in toks] for rid, toks in emitted.items()}

    monkeypatch.setattr(ServingEngine, "step", step)
    out = _measure("tiny.serve.batch", tmp_path)
    assert out["correct"] is False and out["attempted"] > 0


def test_a_train_step_that_leaves_its_state_unchanged_is_not_correct(tmp_path, monkeypatch):
    import paddle_tpu as P

    monkeypatch.setattr(P.optimizer.AdamW, "_update_param", lambda self, p, g, lr, wd: None)
    out = _measure("tiny.train", tmp_path)
    assert out["correct"] is False


def test_a_compile_inside_the_window_is_not_correct(tmp_path, monkeypatch):
    from benchmark.harness.compile_meter import CompileMeter

    real = CompileMeter.since
    monkeypatch.setattr(CompileMeter, "since", lambda self, snap: dict(
        real(self, snap), programs_compiled=1))
    out = _measure("tiny.serve.batch", tmp_path)
    assert out["correct"] is False


# ----------------------------------------------------------------- off a TPU
def test_the_command_fails_without_a_result_off_a_tpu():
    cmd = [sys.executable if w == "python3" else w for w in BENCH["command"]]
    cell = BENCH["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(cmd + ["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=loader.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and "TPU" in p.stderr
