"""The readers of the program's set-up ledger (benchmark/harness/setup_ledger.py
and the six ``setup_*_s`` files under benchmark/layer_metrics) on a small
recorded report, ``recorded_setup_ledger.json``: one start-up of 100 s as
``paddle_tpu.profiler.setup_report()`` tells it, written by ``_record`` below
from the program's own ``SetupLedger`` on a clock the test moves."""
import json
import os

import pytest

from benchmark.harness import loader, setup_ledger

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = {"setup_import_s": 4.0, "setup_build_s": 14.5, "setup_trace_s": 12.6,
           "setup_backend_s": 33.4, "setup_run_s": 27.0, "setup_outside_s": 8.5}
# what the recorded start-up left unnamed: jax's start (3 s), the harness
# before the model (3 s), make_weights and assign less their one compile (2 s),
# the frontend's return to the first launch (0.5 s)
UNNAMED_S = 3.0 + 3.0 + 2.0 + 0.5


def _record():
    """The recorded start-up, replayed through the program's ledger."""
    from paddle_tpu.profiler import _BACKEND, _LOWER, _TRACE, SetupLedger, SetupSpan

    class Clock:
        t = 1000.0

        def __call__(self):
            return self.t

    clock = Clock()
    ledger = SetupLedger(clock=clock)

    def at(t):
        clock.t = t

    def compiled(name, t0, trace, lower, backend):
        at(t0 + trace + lower)
        ledger._on_duration(_TRACE, trace, fun_name=name)
        ledger._on_duration(_LOWER, lower, fun_name=f"jit({name})")
        ledger._on_duration(_BACKEND, backend, fun_name=f"jit({name})")

    def span(name, t0, t1, inside=(), **attrs):
        at(t0)
        with SetupSpan(name, ledger=ledger, **attrs):
            for fn in inside:
                fn()
            at(t1)

    ledger.record("setup.import", 1003.0, 4.0, jax_loaded=True)
    span("model.init", 1010.0, 1020.0, family="LlamaForCausalLM", dtype="bfloat16",
         parameters=7, inside=[lambda: compiled("_normal", 1011.0, 0.1, 0.2, 0.7),
                               lambda: compiled("_uniform", 1013.0, 0.1, 0.1, 0.3)])
    compiled("make", 1021.0, 0.5, 0.5, 2.0)            # the harness's make_weights
    span("engine.init", 1025.0, 1031.0, inside=[
        lambda: span("engine.init.weights", 1025.0, 1029.0, bytes=14, inside=[
            lambda: compiled("convert_element_type", 1026.0, 0.05, 0.05, 0.4)]),
        lambda: span("engine.init.pool", 1029.0, 1030.0, bytes=64),
        lambda: span("engine.init.programs", 1030.0, 1030.5, shared=False)])
    span("frontend.init", 1031.0, 1031.5)
    for name, k, t0, t1, three in (("step", 1, 1032.0, 1052.0, (2.0, 3.0, 14.0)),
                                   ("mega", 2, 1055.0, 1070.0, (1.0, 2.0, 11.5)),
                                   ("mega", 4, 1072.0, 1080.0, (1.0, 2.0, 4.5))):
        compiled(name, t0 + 0.5, *three)
        at(t1)
        ledger.acquired(name, t1 - t0, kind=name, k=k)
    return {"t_open": 1100.0, "setup_s": 100.0, "setup_report": ledger.report(1000.0, 1100.0)}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_setup_ledger.json")) as f:
        return json.load(f)


def _read(metric, run):
    return loader.load_module("layer_metrics", metric).read(run)


def test_the_fixture_is_what_the_programs_ledger_writes(recorded):
    assert json.loads(json.dumps(_record())) == recorded


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_no_ledger_reads_as_nothing(metric, monkeypatch):
    import paddle_tpu.profiler as prof

    assert _read(metric, {}) is None
    assert _read(metric, {"setup_report": None, "t_open": 9.0, "setup_s": 4.0}) is None
    # the parent of the PR that brought the ledger: a profiler without it
    monkeypatch.delattr(prof, "setup_report")
    assert _read(metric, {"t_open": 9.0, "setup_s": 4.0}) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_reading_of_the_recorded_start(recorded, metric):
    assert _read(metric, dict(recorded)) == pytest.approx(METRICS[metric], abs=1e-9)


def test_the_six_sum_to_setup_s_and_outside_is_what_was_left_unnamed(recorded):
    got = setup_ledger.readings(dict(recorded))
    assert tuple(got) == setup_ledger.READINGS
    assert sum(got.values()) == pytest.approx(recorded["setup_s"], abs=1e-6)
    assert got["outside_s"] == pytest.approx(UNNAMED_S, abs=1e-6)
    # a longer set-up with the same ledger: the difference is all outside
    longer = dict(recorded, setup_s=recorded["setup_s"] + 2.5)
    again = setup_ledger.readings(longer)
    assert again["outside_s"] == pytest.approx(UNNAMED_S + 2.5, abs=1e-6)
    assert {k: v for k, v in again.items() if k != "outside_s"} == pytest.approx(
        {k: v for k, v in got.items() if k != "outside_s"})


def test_backend_prints_the_dearest_programs_by_name(recorded, capsys):
    _read("setup_backend_s", dict(recorded))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["setup_programs"]
    assert [(p["program"], p["k"], p["backend_s"], p["cache_hit"]) for p in line["programs"]] == [
        ("step", 1, 14.0, False), ("mega", 2, 11.5, False), ("mega", 4, 4.5, False)]
    assert line["other"] == {"count": 4, "trace_s": 0.75, "lower_s": 0.85, "backend_s": 3.4,
                             "cache_hits": 0}


def test_the_live_ledger_of_this_process_adds_up_too():
    """No recorded report in the run: the reader asks the program, as
    ``benchmark.run`` has it do, over an interval that ends now."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import SETUP, SetupSpan

    t0 = SETUP.clock()
    with SetupSpan("model.init", family="probe", dtype="float32"):
        jax.jit(lambda x: x * 2 + 1)(jnp.ones((5, 3)))
    jnp.zeros((5, 3)).block_until_ready()                 # the harness's own, unnamed
    t1 = SETUP.clock()
    got = setup_ledger.readings({"t_open": t1, "setup_s": t1 - t0})
    assert sum(got.values()) == pytest.approx(t1 - t0, abs=1e-6)
    assert got["build_s"] > 0 and got["backend_s"] > 0 and got["trace_s"] > 0
    assert got["import_s"] == 0.0 and got["run_s"] == 0.0
    assert 0.0 < got["outside_s"] < t1 - t0
