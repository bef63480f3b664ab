"""Set-up, told by the program (paddle_tpu/profiler: ``SetupSpan``,
``SetupLedger``, ``setup_report``): the rows a start-up leaves, the compile
ledger behind the ``jax.monitoring`` listeners, and what an operator reads.

The engine here has a geometry of its own (no other test file builds it), so
that neither ``serving._PROGRAM_CACHE`` nor jax's caches hold its programs
when the module starts: every program it launches is a program acquired."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.profiler as prof
from paddle_tpu.inference import ServingEngine, ServingFrontend
from paddle_tpu.profiler import SETUP, SetupLedger, SetupSpan

ENGINE = dict(max_batch_size=3, max_seq_len=48, block_size=8, token_budget=24, megastep_k=4)
LAUNCH_S = 4.0                 # what a launch costs on the injected clock


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _Slow:
    """A jitted program whose every call costs ``LAUNCH_S`` on the engine's clock."""

    def __init__(self, fn, clock):
        self.fn, self.clock, self.__name__ = fn, clock, fn.__name__

    def _cache_size(self):
        return self.fn._cache_size()

    def __call__(self, *a, **kw):
        self.clock.advance(LAUNCH_S)
        return self.fn(*a, **kw)


def _slow_engine(model, clock):
    eng = ServingEngine(model, clock=clock, **ENGINE)
    for kind, (attr, build) in {"step": ("_step_fn", "_build_step"),
                                "mega": ("_mega_fn", "_build_megastep"),
                                "mixed": ("_mixed_fn", "_build_mixed_megastep")}.items():
        fn = eng._programs.setdefault(kind, None) or getattr(eng, build)()
        eng._programs[kind] = fn
        setattr(eng, attr, _Slow(fn, clock))
    return eng


def _drive(fe):
    """Prefill step, decode step, decode scans at K 2 and 4, the mixed scan."""
    for new in (2, 3, 5):
        fe.submit([3, 17, 101, 5], max_new_tokens=new)
        fe.run()
    fe.submit([3, 17, 101, 5], max_new_tokens=12)
    fe.step()
    fe.submit(list(range(40, 60)), max_new_tokens=4)
    fe.run()


@pytest.fixture(scope="module")
def started():
    """One start-up: a model, an engine behind a frontend driven through its
    programs, a second engine of the same geometry, a train step called twice.
    -> the rows and compile rows it left, and the objects."""
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion

    def rows_after(last):
        return [r for r in SETUP.rows if r["id"] > last]

    def last_id():
        return max((r["id"] for r in SETUP.rows), default=0)

    set_hybrid_communicate_group(None)
    row0 = last_id()
    since = SETUP.clock()
    P.seed(5)
    cfg = LlamaConfig(vocab_size=96, hidden_size=96, intermediate_size=160,
                      num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    model.eval()
    clock = FakeClock(100.0)
    eng = _slow_engine(model, clock)
    fe = ServingFrontend([eng], clock=clock)
    _drive(fe)
    first, row1 = rows_after(row0), last_id()
    eng2 = _slow_engine(model, clock)
    eng2.add_request([3, 17, 101, 5], max_new_tokens=6)
    eng2.run()
    second, row2 = rows_after(row1), last_id()

    trained = LlamaForCausalLM(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=trained.parameters())
    crit = LlamaPretrainingCriterion()
    step = P.jit.TrainStep(trained, lambda m, ids: crit(m(ids), ids), opt)
    ids = P.to_tensor(np.arange(22, dtype="int32").reshape(2, 11) % 96)
    step(ids)
    step(ids)
    return {"first": first, "second": second, "train": rows_after(row2), "model": model,
            "eng": eng, "eng2": eng2, "report": prof.setup_report(since), "since": since}


def _named(rows, name):
    return [r for r in rows if r["name"] == name]


# ------------------------------------------------------------ the span rows
@pytest.mark.parametrize("name,parent,attrs", [
    ("model.init", None, {"family": "LlamaForCausalLM", "dtype": "float32"}),
    ("engine.init", None, {}),
    ("engine.init.weights", "engine.init", {}),
    ("engine.init.pool", "engine.init", {"bytes": 2 * 18 * 2 * 8 * 24 * 4}),
    ("engine.init.programs", "engine.init", {"shared": False}),
    ("frontend.init", None, {}),
])
def test_a_start_leaves_one_row_a_stage_under_its_parent(started, name, parent, attrs):
    (row,) = _named(started["first"], name)
    by_id = {r["id"]: r for r in started["first"]}
    assert (by_id[row["parent"]]["name"] if row["parent"] else None) == parent
    assert attrs.items() <= row["attrs"].items()
    assert row["seconds"] >= 0.0
    if name == "model.init":
        assert row["attrs"]["parameters"] == started["model"].num_params > 0
    if name == "engine.init.weights":
        assert row["attrs"]["bytes"] >= 4 * started["model"].num_params


def test_the_stages_come_in_order_and_children_lie_inside(started):
    rows = [r for r in started["first"] if r["name"] != "program.acquire"]
    assert [r["name"] for r in sorted(rows, key=lambda r: (r["t0"], r["id"]))] == [
        "model.init", "engine.init", "engine.init.weights", "engine.init.pool",
        "engine.init.programs", "frontend.init"]
    (init,) = _named(rows, "engine.init")
    kids = [r for r in rows if r["parent"] == init["id"]]
    assert all(init["t0"] <= k["t0"] and k["t0"] + k["seconds"] <= init["t0"] + init["seconds"]
               for k in kids)
    assert sum(k["seconds"] for k in kids) <= init["seconds"]


def test_one_acquisition_a_program_in_order_on_the_injected_clock(started):
    got = _named(started["first"], "program.acquire")
    assert [(r["attrs"]["program"], r["attrs"]["kind"], r["attrs"]["k"]) for r in got] == [
        ("step", "step", 1),        # the prefill step (mq = T)
        ("step", "step", 1),        # the decode step (mq = 1)
        ("mega", "mega", 2), ("mega", "mega", 4), ("mixed", "mixed", 4)]
    # the row's seconds are the engine.launch phase's, on the engine's clock
    assert [r["seconds"] for r in got] == [LAUNCH_S] * 5
    assert [r["t0"] for r in got] == sorted(r["t0"] for r in got)
    assert all(r["parent"] is None for r in got)
    frontend = _named(started["first"], "frontend.init")[0]
    assert frontend["id"] < got[0]["id"]


@pytest.mark.parametrize("program", ["step", "mega", "mixed"])
def test_the_compile_ledger_names_each_program_with_its_three_times(started, program):
    rows = [c for c in started["report"]["compiles"] if c["fun_name"] == program]
    # ``step`` at mq = T and 1 and the train step's, ``mega`` at K = 2 and 4
    want = {"step": 3, "mega": 2, "mixed": 1}[program]
    assert len(rows) == want and all(c["acquired"] for c in rows)
    for c in rows:
        assert c["trace_s"] > 0 and c["lower_s"] > 0 and c["backend_s"] > 0
        assert isinstance(c["cache_hit"], bool) and c["t0"] >= started["since"]
    mine = [p for p in started["report"]["programs"] if p["program"] == program]
    assert sorted(p["backend_s"] for p in mine) == sorted(c["backend_s"] for c in rows)


def test_a_second_engine_of_the_geometry_shares_and_acquires_nothing(started):
    names = [r["name"] for r in started["second"]]
    assert names == ["engine.init.weights", "engine.init.pool", "engine.init.programs",
                     "engine.init"]
    assert _named(started["second"], "engine.init.programs")[0]["attrs"]["shared"] is True
    assert started["eng2"].launches > 0
    assert started["eng2"].state_summary()["setup"]["programs"] == []


def test_state_summary_gives_the_engines_own_stages_and_programs(started):
    own = started["eng"].state_summary()["setup"]
    assert [s["name"] for s in own["stages"]] == [
        "engine.init", "engine.init.weights", "engine.init.pool", "engine.init.programs"]
    assert own["stages"][0] is _named(started["first"], "engine.init")[0]
    assert own["stages"][1:] == [r for r in started["first"]
                                 if r["name"].startswith("engine.init.")]
    assert own["programs"] == _named(started["first"], "program.acquire")
    assert all(p["attrs"]["backend_s"] > 0 for p in own["programs"])


def test_train_step_leaves_its_init_and_one_acquisition(started):
    rows = started["train"]
    assert [r["name"] for r in rows] == ["model.init", "train_step.init", "program.acquire"]
    init, acquire = rows[1], rows[2]
    params = rows[0]["attrs"]["parameters"]
    assert init["attrs"]["bytes"] >= 2 * 4 * params        # two float32 moments
    assert acquire["attrs"]["program"] == "step" and acquire["attrs"]["kind"] == "train"
    assert acquire["attrs"]["k"] == 1 and acquire["seconds"] > 0
    for k in ("trace_s", "lower_s", "backend_s"):
        assert 0 < acquire["attrs"][k] < acquire["seconds"]


def test_train_step_names_each_call_that_compiles_until_one_compiles_nothing():
    """On the chip the SECOND call compiles the step again (its inputs are the
    first call's outputs, placed as the fresh state was not): it is named too,
    and after one call that compiled nothing the step stops looking."""
    net = P.nn.Linear(6, 3)
    opt = P.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = P.jit.TrainStep(net, lambda m, x: (m(x) ** 2).mean(), opt)
    x = P.to_tensor(np.ones((4, 6), "float32"))
    last = max(r["id"] for r in SETUP.rows)
    step(x)

    class Grows:
        """The compiled step, with a cache that grows on the calls named."""

        def __init__(self, fn, on_calls):
            self.fn, self.on_calls, self.calls, self.__name__ = fn, on_calls, 1, fn.__name__

        def _cache_size(self):
            return sum(c <= self.calls for c in self.on_calls)

        def __call__(self, *a):
            self.calls += 1
            return self.fn(*a)

    step._compiled = Grows(step._compiled, on_calls=(1, 2, 4))
    for _ in range(3):
        step(x)
    rows = [r for r in SETUP.rows if r["id"] > last and r["name"] == "program.acquire"]
    assert [(r["attrs"]["program"], r["attrs"]["kind"]) for r in rows] == [("step", "train")] * 2
    assert "backend_s" in rows[0]["attrs"] and "backend_s" not in rows[1]["attrs"]
    assert step._acquiring is False and step._compiled.calls == 4


def test_import_row_spans_the_package_and_says_whether_jax_came_first():
    (row,) = [r for r in SETUP.rows if r["name"] == "setup.import"] or [None]
    if row is None:
        pytest.skip("the bounded ledger has dropped the import's row by now")
    assert row["parent"] is None and row["seconds"] > 0
    assert isinstance(row["attrs"]["jax_loaded"], bool)


def test_an_eager_helper_goes_under_other(started):
    import jax
    import jax.numpy as jnp

    def setup_ledger_eager_helper(x):
        return x * 3 + 1

    since = SETUP.clock()
    jax.jit(setup_ledger_eager_helper)(jnp.ones((7, 13)))
    rep = prof.setup_report(since)
    assert rep["programs"] == []
    assert rep["other"]["count"] >= 1 and rep["other"]["backend_s"] > 0
    assert "setup_ledger_eager_helper" in [n for n, _, _ in rep["other"]["by_name"]]
    (row,) = [c for c in rep["compiles"] if c["fun_name"] == "setup_ledger_eager_helper"]
    assert row["acquired"] is False and row["trace_s"] > 0 and row["lower_s"] > 0
    # the fixture's own start had such helpers too (the model's initialisers)
    assert started["report"]["other"]["count"] > 0


# ------------------------------------------- the ledger, on a clock of its own
def test_a_set_up_span_is_a_record_event_in_the_trace(host_spans):
    ledger = SetupLedger()
    with host_spans("probe.") as events:
        with SetupSpan("probe.outer", ledger=ledger, size=3) as outer:
            with SetupSpan("probe.inner", ledger=ledger):
                pass
            outer.note(bytes=12)
    assert [e[0] for e in events] == ["probe.outer", "probe.inner"]
    assert events[0][1] <= events[1][1] and events[1][2] <= events[0][2]
    assert events[0][3]["size"] == 3
    inner, outer = ledger.rows
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert outer["attrs"] == {"size": 3, "bytes": 12}
    assert not [r for r in SETUP.rows if r["name"].startswith("probe.")]


def test_the_steady_state_spans_write_no_rows():
    before = (len(SETUP.rows), SETUP.dropped_rows)
    with prof.RecordEvent("engine.launch", kind="mega", k=8):
        pass
    assert (len(SETUP.rows), SETUP.dropped_rows) == before


def test_a_decorated_constructor_opens_a_fresh_span_a_call_and_closes_it_on_error():
    ledger = SetupLedger()

    class Thing:
        @SetupSpan("thing.init", ledger=ledger)
        def __init__(self, ok=True):
            self.span = ledger.innermost()
            if not ok:
                raise ValueError("no")

    a, b = Thing(), Thing()
    with pytest.raises(ValueError):
        Thing(ok=False)
    assert ledger.innermost() is None
    assert [r["name"] for r in ledger.rows] == ["thing.init"] * 3
    assert a.span is not b.span and a.span.row is ledger.rows[0]
    assert len({r["id"] for r in ledger.rows}) == 3


def _feed(ledger, name, *, trace=0.5, lower=0.25, backend=2.0, hit=False, read=None,
          lowering=True):
    """The events jax fires for one program, in jax's order."""
    if lowering:
        ledger._on_duration(prof._TRACE, 0.01, fun_name="inner_helper")
        ledger._on_duration(prof._TRACE, trace, fun_name=name)
        ledger._on_duration(prof._TRACE, 0.02, fun_name="less")      # the lowering's own
        ledger._on_duration(prof._LOWER, lower, fun_name=f"jit({name})")
    if hit:
        ledger._on_event(prof._CACHE_HIT)
        ledger._on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)
        ledger._on_duration(prof._CACHE_READ, read)
    ledger._on_duration(prof._BACKEND, backend, fun_name=f"jit({name})")


@pytest.mark.parametrize("case,kw,want", [
    ("compiled", {}, {"trace_s": 0.5, "lower_s": 0.25, "backend_s": 2.0, "cache_hit": False,
                      "cache_read_s": 0.0}),
    ("read_back", {"hit": True, "read": 0.75, "backend": 0.8},
     {"trace_s": 0.5, "lower_s": 0.25, "backend_s": 0.8, "cache_hit": True,
      "cache_read_s": 0.75}),
    ("lowering_kept", {"lowering": False},
     {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 2.0, "cache_hit": False,
      "cache_read_s": 0.0}),
])
def test_a_compile_row_from_jaxs_events(case, kw, want):
    clock = FakeClock(50.0)
    ledger = SetupLedger(clock=clock)
    _feed(ledger, "mega", **kw)
    (row,) = ledger.compiles
    assert {k: row[k] for k in want} == want
    assert row["fun_name"] == "mega" and row["acquired"] is False
    assert row["t0"] == 50.0 - (want["trace_s"] + want["lower_s"] if kw.get("lowering", True)
                                else want["backend_s"])
    # a hit between two programs belongs to neither
    ledger._on_event(prof._CACHE_HIT)
    _feed(ledger, "mixed")
    assert ledger.compiles[-1]["cache_hit"] is False
    # the acquisition claims the newest unclaimed row of its name, once
    clock.advance(10.0)
    got = ledger.acquired("mega", 3.0, kind="mega", k=8)
    assert got["t0"] == 57.0 and got["seconds"] == 3.0
    assert got["attrs"]["backend_s"] == want["backend_s"] and row["acquired"] is True
    again = ledger.acquired("mega", 1.0, kind="mega", k=2)
    assert "backend_s" not in again["attrs"]


@pytest.mark.parametrize("kind,capacity,fed,kept,dropped", [
    ("rows", 4, 6, 4, 2), ("rows", 4, 4, 4, 0), ("compiles", 2, 11, 8, 3)])
def test_the_ledger_is_bounded_and_counts_what_it_drops(kind, capacity, fed, kept, dropped):
    ledger = SetupLedger(capacity=capacity, clock=FakeClock())
    for i in range(fed):
        if kind == "rows":
            ledger.record(f"stage.{i}", float(i), 1.0)
        else:
            _feed(ledger, f"program_{i}")
    held = ledger.rows if kind == "rows" else ledger.compiles
    assert len(held) == kept
    assert (ledger.dropped_rows if kind == "rows" else ledger.dropped_compiles) == dropped
    last = held[-1]
    assert (last["name"] if kind == "rows" else last["fun_name"]).endswith(str(fed - 1))
    assert ledger.report()["dropped"][kind] == dropped


@pytest.mark.parametrize("since,until", [(None, None), (0.0, 30.0), (2.5, 11.0), (12.0, 13.0)])
def test_stages_and_remainder_sum_to_the_interval_asked_for(since, until):
    clock = FakeClock()
    ledger = SetupLedger(clock=clock)
    clock.advance(1.0)
    with SetupSpan("outer", ledger=ledger):             # 1 .. 8
        clock.advance(2.0)
        with SetupSpan("inner", ledger=ledger):         # 3 .. 7
            clock.advance(3.5)
            _feed(ledger, "eager", trace=0.25, lower=0.25, backend=0.5)   # t0 = 6.0
            clock.advance(0.5)
        clock.advance(1.0)
    clock.advance(2.0)
    with SetupSpan("later", ledger=ledger):             # 10 .. 14
        clock.advance(4.0)
    rep = ledger.report(since, until)
    lo, hi = (1.0 if since is None else since), (14.0 if until is None else until)
    assert (rep["since"], rep["until"], rep["seconds"]) == (lo, hi, hi - lo)
    top = [s for s in rep["stages"] if s["parent"] is None]
    named = sum(min(s["t0"] + s["seconds"], hi) - max(s["t0"], lo) for s in top)
    assert named + rep["unnamed_s"] == pytest.approx(hi - lo)
    want = {(None, None): 2.0, (0.0, 30.0): 19.0, (2.5, 11.0): 2.0, (12.0, 13.0): 0.0}
    assert rep["unnamed_s"] == pytest.approx(want[(since, until)])
    if since != 12.0:
        outer, inner = rep["stages"][0], rep["stages"][1]
        assert (outer["name"], outer["self_s"], outer["compile_s"]) == ("outer", 3.0, 1.0)
        assert (inner["name"], inner["self_s"], inner["compile_s"]) == ("inner", 4.0, 1.0)
        assert rep["other"]["count"] == 1 and rep["other"]["backend_s"] == 0.5
    assert rep["text"].splitlines()[0].startswith(f"set-up: {hi - lo:.2f} s")


def test_a_call_claims_no_compile_row_from_before_it_began():
    """An engine's ``step`` compiled and left unclaimed (a lowering compiled by
    hand, a described-device compile) is no train step's: ``acquired(since=)``
    claims only a row that began after the call did."""
    ledger = SetupLedger()
    ledger.compiles.append({"fun_name": "step", "trace_s": 0.1, "lower_s": 0.1, "backend_s": 0.5,
                            "cache_hit": True, "cache_read_s": 0.06, "t0": ledger.clock() - 60.0,
                            "acquired": False})
    t0 = ledger.clock()
    row = ledger.acquired("step", 0.0, since=t0, kind="train", k=1)
    assert "backend_s" not in row["attrs"] and not ledger.compiles[-1]["acquired"]
    assert "backend_s" in ledger.acquired("step", 0.0, kind="step", k=1)["attrs"]
