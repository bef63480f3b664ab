"""A launch crosses the host-device boundary once each way (ISSUE 35): the
control rows go up as ONE packed ``int32`` block, what the host reads comes
down as ONE, the log-probabilities only when a row asked.

The packer round-trips every row of every launch kind; an engine of each
family, driven through the step, mega, mixed and spec programs with greedy
and sampled rows, yields what the PARENT's engine yielded from the same
requests with its fifteen separate arguments: ``tests/data/
launch_block_parent.json``, recorded at commit b2330d2 by this file's own
``scenario`` (``PYTHONPATH=<a checkout of b2330d2> python
tests/test_launch_block.py``, jax 0.9.0 on the CPU); and the engine's own
counters say how often a launch crossed."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine

import programs
from programs import ENGINE

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "launch_block_parent.json")
TRUNK_COUNTS = ("attn_positions_live", "attn_positions_read", "attn_rows_kernel",
                "kv_write_tokens", "kv_write_blocks", "moe_tokens", "moe_local_picks",
                "loop_tokens", "loop_token_passes", "dsa_queries", "dsa_positions_scored",
                "dsa_positions_selected", "dsa_positions_read")
COUNTERS = TRUNK_COUNTS + ("launches", "megasteps", "megasteps_mixed", "megastep_tokens",
                           "prefill_chunks", "prefill_tokens_computed",
                           "spec_verify_forwards", "spec_draft_tokens",
                           "spec_accepted_tokens")
SAMPLED = {"temperature": 0.8, "top_k": 5, "top_p": 0.9, "seed": 11, "logprobs": True}


@pytest.fixture(autouse=True)
def _no_fleet_group():
    set_hybrid_communicate_group(None)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _model(name):
    """The sub-tiny model of a scenario's family: the one whose programs are pinned."""
    return programs.pinned_model("deepseek" if name == "dsv32" else name.partition("-")[0])


SCENARIOS = ("llama", "llama-int8", "llama-deadline", "pangu", "ouro", "dsv32")


def scenario(name):
    """One engine of ``name`` through every program it can launch: a greedy
    row prefilled by the step program, a SAMPLED row with log-probabilities
    that arrives while the first decodes (the mixed scan, then the decode
    scan), a repetitive greedy prompt (verify launches where ``spec_k``), a
    greedy row with log-probabilities. -> what a caller sees of it."""
    kw = {"spec_k": 2}
    clock = None
    if name == "llama-int8":
        kw = {"cache_quant": "int8"}
    if name == "llama-deadline":
        clock = FakeClock()
        kw = {"spec_k": 2, "clock": clock, "deadline_token_seconds": 1.0}
    eng = ServingEngine(_model(name), **{**ENGINE, **kw})
    kinds = []
    launch_phase = eng._launch_phase

    def launched(kind, *a, **k):
        kinds.append(kind)
        return launch_phase(kind, *a, **k)

    eng._launch_phase = launched
    out = {}

    def drain():
        # as ``run()``, on the injected clock: 2 s a step, and a row that its
        # deadline froze (slot -1) waits for the control plane, not for us
        while eng._queue or any(r.slot >= 0 for r in eng._active.values()):
            if clock is not None:
                clock.t += 2.0
            for rid, toks in eng.step().items():
                out.setdefault(rid, []).extend(int(t) for t in toks)

    rids = [eng.add_request([3, 17, 101], max_new_tokens=12, eos_token_id=1,
                            sampling={"spec": False},
                            deadline_s=6.5 if clock is not None else None)]
    out[rids[0]] = [int(t) for t in eng.step()[rids[0]]]
    rids.append(eng.add_request([40, 41, 42, 43, 44, 45, 46, 47, 48, 49], max_new_tokens=7,
                                sampling=dict(SAMPLED, spec=False)))
    drain()
    rids.append(eng.add_request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=24))
    rids.append(eng.add_request([9, 8, 7, 6, 5], max_new_tokens=5,
                                sampling={"logprobs": True}))
    drain()
    lps = eng.pop_token_logprobs()
    return {"kinds": kinds,
            "tokens": [out.get(r, []) for r in rids],
            "frozen_by_its_deadline": sorted(rids.index(r) for r in eng._active),
            "logprobs": [[float(v) for v in lps.get(r, [])] for r in rids],
            "counters": {c: int(getattr(eng, c)) for c in COUNTERS},
            "crossings": (getattr(eng, "control_arrays_up", None),
                          getattr(eng, "result_reads", None))}


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.skipif(jax.__version__ != "0.9.0", reason="the recording is jax 0.9.0's")
@pytest.mark.parametrize("name", SCENARIOS)
def test_an_engine_yields_what_the_parents_unpacked_arguments_yielded(golden, name):
    got, want = scenario(name), golden[name]
    assert got["kinds"] == want["kinds"]
    # every program the family can launch ran
    assert set(got["kinds"]) == ({"step", "mega"} if name == "llama-int8"
                                 else {"step", "mega", "mixed", "spec"})
    assert got["tokens"] == want["tokens"]
    assert got["frozen_by_its_deadline"] == want["frozen_by_its_deadline"] == (
        [0] if name == "llama-deadline" else [])
    for g, w in zip(got["logprobs"], want["logprobs"]):
        assert len(g) == len(w)
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5)
    assert [len(l) for l in got["logprobs"]] == [
        0, len(got["tokens"][1]), 0, len(got["tokens"][3])]
    assert got["counters"] == want["counters"]
    dense = ("attn_positions_live", "attn_positions_read", "kv_write_tokens")
    moved = {"pangu": ("moe_tokens", "moe_local_picks"),
             "ouro": dense + ("loop_tokens", "loop_token_passes"),
             "dsv32": ("attn_positions_live", "moe_tokens", "dsa_queries",
                       "dsa_positions_scored", "dsa_positions_selected",
                       "dsa_positions_read")}.get(name, dense)
    assert all(got["counters"][c] > 0 for c in moved), got["counters"]
    # one control array up a launch; one read down, one more where a
    # scheduled row asked for log-probabilities
    up, reads = got["crossings"]
    n = got["counters"]["launches"]
    assert up == n and n <= reads <= 2 * n


# ------------------------------------------------------------ the packer
def _rows_of(layout, seed):
    """Rows that would betray a row misplaced or a bit lost: every word
    distinct; floats that no int32 round trip keeps (a denormal, -0.0, 1 ulp
    off 1.0, inf); negative ints; flags on and off."""
    rng = np.random.default_rng(seed)
    rows = {}
    for name, shape, kind in layout.rows:
        n = int(np.prod(shape))
        if kind == "f":
            a = rng.standard_normal(n).astype(np.float32)
            a[:4] = [np.float32(1e-42), -0.0, np.nextafter(np.float32(1), np.float32(2)),
                     np.inf][:len(a[:4])]
        elif kind == "b":
            a = rng.integers(0, 2, n).astype(bool)
        else:
            a = rng.integers(-2 ** 31, 2 ** 31 - 1, n, dtype=np.int64).astype(np.int32)
        rows[name] = a.reshape(shape)
    return rows


LAYOUTS = {"step-decode": ("step", 4), "step-prefill": ("step", 32), "mega": ("mega", 0),
           "mixed": ("mixed", 4 * 8), "spec": ("spec", 2)}


@pytest.mark.parametrize("case", sorted(LAYOUTS))
def test_the_packer_round_trips_every_row(case):
    from paddle_tpu.inference.serving import control_layout

    kind, n = LAYOUTS[case]
    B, P_ = 4, 12
    layout = control_layout(kind, B, P_, n)
    rows = _rows_of(layout, seed=len(case))
    if "eos" in rows:
        rows["eos"][:] = [-1, 250, -1, 2]                 # no EOS is -1
    rows["bt"][1:] = -1                                   # rows without blocks
    if "prompt_buf" in rows:
        rows["prompt_buf"][2, 20:] = 0                    # a window's zero-padded tail
    block = layout.pack(rows)
    assert block.dtype == np.int32 and block.shape == (layout.size,)
    assert layout.size == sum(a.size for a in rows.values())

    def same_bits(got, want):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        if want.dtype == np.float32:
            got, want = got.view(np.int32), want.view(np.int32)
        np.testing.assert_array_equal(got, want)

    for name, a in layout.unpack(block).items():          # on the host: views
        same_bits(a, rows[name])
    in_graph = jax.jit(layout.unpack)(block)              # in a program: slices
    assert set(in_graph) == set(rows)
    for name, a in in_graph.items():
        same_bits(a, rows[name])
    # and packed by a program, the block is the host's word for word
    np.testing.assert_array_equal(
        np.asarray(jax.jit(layout.pack)({k: jnp.asarray(v) for k, v in rows.items()})), block)


def test_the_packer_refuses_a_wrong_row():
    from paddle_tpu.inference.serving import control_layout

    layout = control_layout("mega", 4, 12)
    rows = _rows_of(layout, 0)
    with pytest.raises(ValueError, match="shape"):
        layout.pack(dict(rows, toks=np.zeros((5,), np.int32)))
    with pytest.raises(ValueError, match="rows"):
        layout.pack({k: v for k, v in rows.items() if k != "dl"})
    with pytest.raises(ValueError, match="words"):
        layout.unpack(np.zeros((layout.size + 1,), np.int32))


def test_a_result_block_carries_its_layout_through_jit():
    from paddle_tpu.inference.launch_block import ResultBlock

    @jax.jit
    def program(x):
        return ResultBlock.of({"toks": x, "valid": x > 1},
                              {"b_count": jnp.sum(x), "a_count": jnp.int32(-7)})

    res = program(jnp.arange(6, dtype=jnp.int32).reshape(2, 3))
    assert [r[0] for r in res.layout.rows] == ["toks", "valid", "a_count", "b_count"]
    rows, counts = res.read()
    assert counts == {"a_count": -7, "b_count": 15}
    np.testing.assert_array_equal(rows["toks"], np.arange(6).reshape(2, 3))
    assert rows["valid"].dtype == np.bool_ and rows["valid"].sum() == 4


# ------------------------------------------------- the engine's own counters
@pytest.fixture(scope="module")
def llama():
    return programs.pinned_model("llama")


@pytest.mark.parametrize("logprobs", [False, True])
def test_a_launch_crosses_once_each_way(llama, logprobs):
    eng = ServingEngine(llama, spec_k=2, **ENGINE)
    sampling = {"logprobs": True} if logprobs else None
    eng.add_request([3, 17, 101], max_new_tokens=12, sampling=sampling)
    eng.step()
    eng.add_request([40, 41, 42, 43, 44, 45, 46, 47, 48, 49], max_new_tokens=6,
                    sampling=sampling)
    eng.run()
    eng.add_request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=24, sampling=sampling)
    eng.run()
    launch = eng.state_summary()["launch"]
    assert set(launch) == {"launches", "control_arrays_up", "result_reads"}
    assert launch["launches"] == eng.launches > 4
    assert launch["control_arrays_up"] / launch["launches"] <= 2
    assert launch["result_reads"] / launch["launches"] == (2 if logprobs else 1)


def test_only_the_launches_of_a_row_that_asked_read_logprobs(llama):
    eng = ServingEngine(llama, **ENGINE)
    eng.add_request([3, 17, 101], max_new_tokens=9)
    eng.run()
    alone = eng.launches
    assert eng.result_reads == alone
    rid = eng.add_request([5, 6, 7], max_new_tokens=9, sampling={"logprobs": True})
    eng.run()
    assert eng.result_reads - alone == 2 * (eng.launches - alone)
    assert len(eng.pop_token_logprobs()[rid]) == 9


def test_the_spans_carry_what_crossed(llama, host_spans):
    eng = ServingEngine(llama, **ENGINE)
    eng.add_request([3, 17, 101], max_new_tokens=2)
    eng.run()                                             # compiles, untraced
    with host_spans("engine.") as events:
        eng.add_request([3, 17, 101], max_new_tokens=9)
        eng.step()
        eng.add_request([40, 41, 42, 43, 44, 45, 46, 47, 48, 49], max_new_tokens=4,
                        sampling={"logprobs": True})
        eng.run()
    launches = [e[3] for e in events if e[0] == "engine.launch"]
    waits = [e[3] for e in events if e[0] == "engine.wait"]
    assert len(launches) == len(waits) >= 3
    from paddle_tpu.inference.serving import control_layout

    sizes = {"step": {control_layout("step", eng.B, eng.P, n).size * 4
                      for n in (eng.B, eng.T)},
             "mega": {control_layout("mega", eng.B, eng.P).size * 4},
             "mixed": {control_layout("mixed", eng.B, eng.P, eng.megastep_k * eng.pc).size * 4}}
    for l in launches:
        assert int(l["arrays_up"]) == 1 and int(l["bytes_up"]) in sizes[l["kind"]]
    assert {int(w["reads"]) for w in waits} == {1, 2}


if __name__ == "__main__":
    # record the parent's answers: run with PYTHONPATH at a checkout of b2330d2
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w") as f:
        json.dump({name: scenario(name) for name in SCENARIOS}, f, indent=1)
    print("recorded", GOLDEN, "with", P.__file__)
