"""HLO dump + device memory stats (reference: paddle/fluid/memory/stats.h,
paddle/cinn/hlir/framework/pir_compiler.h — the "see what got compiled"
capability)."""
import functools
import glob
import os

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import nn


@pytest.mark.quick
def test_memory_stats_api_shape():
    import paddle_tpu.device as device

    # CPU PJRT may report empty stats; the API contract is ints, no raise.
    assert isinstance(device.memory_stats(), dict)
    assert isinstance(device.memory_allocated(), int)
    assert isinstance(device.max_memory_allocated(), int)
    assert isinstance(device.memory_reserved(), int)
    assert isinstance(device.max_memory_reserved(), int)
    info = device.get_memory_info()
    assert set(info) == {"total", "used", "free"}
    device.reset_max_memory_allocated()
    device.reset_max_memory_reserved()
    # after reset, peaks track observations monotonically
    a = device.max_memory_allocated()
    _ = P.randn([64, 64])
    assert device.max_memory_allocated() >= a
    device.empty_cache()


def test_hlo_dump_trainstep_and_to_static(tmp_path):
    d = str(tmp_path / "hlo")
    P.set_flags({"FLAGS_dump_hlo": d})
    try:
        model = nn.Linear(8, 4)
        opt = P.optimizer.SGD(0.1, parameters=model.parameters())
        step = P.jit.TrainStep(
            model, lambda m, x, y: P.nn.functional.mse_loss(m(x), y), opt)
        step(P.randn([4, 8]), P.randn([4, 4]))

        fn = P.jit.to_static(lambda x: x * 2 + 1)
        fn(P.randn([3]))
    finally:
        P.set_flags({"FLAGS_dump_hlo": ""})

    shlo = sorted(glob.glob(os.path.join(d, "*.stablehlo.txt")))
    opt_files = sorted(glob.glob(os.path.join(d, "*.optimized.txt")))
    assert len(shlo) >= 2, shlo
    assert len(opt_files) >= 2, opt_files
    text = open(shlo[0]).read()
    assert "module" in text  # StableHLO module text
    opt_text = open(opt_files[0]).read()
    assert "HloModule" in opt_text or "fusion" in opt_text or "unavailable" in opt_text


def test_lower_text_programmatic():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.hlo_dump import lower_text

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    shlo, opt = lower_text(f, np.ones((4,), np.float32))
    assert "sine" in shlo or "sin" in shlo
    assert opt is not None


def test_device_cuda_parity_surface():
    """paddle.device.cuda facade (streams/events/properties over XLA)."""
    import time

    import paddle_tpu.device.cuda as cuda

    assert cuda.device_count() >= 1
    s = cuda.current_stream()
    ev1 = s.record_event()
    time.sleep(0.01)
    ev2 = cuda.Event()
    ev2.record()
    assert ev1.query() and ev2.query()
    assert ev1.elapsed_time(ev2) >= 5.0  # ms
    with cuda.stream_guard(cuda.Stream()) as st:
        assert cuda.current_stream() is st
        st.synchronize()
    props = cuda.get_device_properties()
    assert cuda.get_device_name()
    assert isinstance(cuda.memory_allocated(), int)
    assert cuda.get_device_capability() == (0, 0)


# ------------------------------------ the program's spans, scopes and names
def test_record_event_takes_attributes_and_records_only_under_a_profiler(monkeypatch):
    import paddle_tpu.profiler as prof

    def no_clock():
        raise AssertionError("RecordEvent read a clock with no Profiler collecting")

    assert prof.RecordEvent._active_sink is None
    monkeypatch.setattr(prof.time, "perf_counter", no_clock)
    with prof.RecordEvent("engine.launch", kind="mixed", k=8, launch=3, t_mono=1.5):
        pass
    ev = prof.RecordEvent("plain")
    ev.begin()
    ev.end()
    monkeypatch.undo()
    with prof.Profiler(timer_only=True) as p:
        with prof.RecordEvent("engine.wait", k=8):
            pass
    assert [name for name, _, _ in p._host_events] == ["engine.wait"]
    assert prof.RecordEvent._active_sink is None


TRAIN_SCOPES = ("embed", "norm", "attn_proj", "attention", "attention/flash_attention",
                "attn_out", "mlp", "head", "loss", "optimizer")


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "grad_scaler"])
def test_lowered_train_step_names_its_scopes(scaled):
    """The guard that makes a refactor which drops a scope fail here instead
    of silently zeroing a per-layer metric; forward, backward and the
    recomputation keep the scope inside jax's jvp / transpose / checkpoint
    wrappers."""
    import re

    import jax.numpy as jnp

    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.framework.random import default_generator
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         LlamaPretrainingCriterion)

    set_hybrid_communicate_group(None)
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=1,
        num_attention_heads=2, max_position_embeddings=64, recompute=True))
    opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    crit = LlamaPretrainingCriterion()
    scaler = P.amp.GradScaler(init_loss_scaling=8.0) if scaled else None
    step = P.jit.TrainStep(model, lambda m, ids: crit(m(ids), ids), opt, scaler=scaler)
    ids = P.to_tensor(np.random.default_rng(0).integers(1, 128, (2, 16)).astype(np.int32))
    assert np.isfinite(float(step(ids).numpy()))
    accs, masters = step._get_opt_state()
    text = step._compiled.lower(
        [p._value for p in step._params], accs, masters,
        [b._value for b in step._buffers], step._scaler_state(),
        default_generator().next_key(), (ids._value,),
        jnp.asarray(1e-3, jnp.float32)).as_text(debug_info=True)
    want = TRAIN_SCOPES + (("grad_unscale",) if scaled else ())
    missing = [s for s in want if not re.search(rf'["/(]{s}[/)"]', text)]
    assert not missing, f"no operation under {missing}"
    for wrapper in ("jvp(loss)", "transpose(jvp(loss))", "rematted_computation/mlp"):
        assert wrapper in text


KERNEL_NAMES = {
    "flash_attention.py": ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"],
    "expert_gmm.py": ["expert_gmm"],
    "fused_norm.py": ["rms_norm", "rms_norm_residual"],
    "fused_ops.py": ["fused_rope", "swiglu_fwd", "swiglu_bwd"],
    "int8_matmul.py": ["int8_matmul"],
    "latent_rows.py": ["latent_rows"],
    "paged_chunk.py": ["paged_chunk"],
    "paged_decode.py": ["paged_decode"],
    "paged_write.py": ["paged_write"],
}


def _pallas_dir():
    import paddle_tpu.ops.pallas as pallas

    return os.path.dirname(pallas.__file__)


def _pallas_calls(tree):
    """The file's ``pallas_call``s in line order."""
    import ast

    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute) and n.func.attr == "pallas_call"]
    return sorted(calls, key=lambda c: c.lineno)


@pytest.mark.parametrize("filename", sorted(KERNEL_NAMES))
def test_every_pallas_call_has_its_own_name(filename):
    """A kernel's device event is named by ``pallas_call(name=)``; without
    one it takes the name of the transform it was traced under. The flash
    kernels' names are what the benchmark's kernel metrics match."""
    import ast

    def names(path):
        out = []
        for c in _pallas_calls(ast.parse(open(path).read())):
            kw = {k.arg: k.value for k in c.keywords}
            assert isinstance(kw.get("name"), ast.Constant), f"{path}:{c.lineno} has no name"
            out.append(kw["name"].value)
        return out

    here = _pallas_dir()
    assert names(os.path.join(here, filename)) == KERNEL_NAMES[filename]
    every = [n for f in glob.glob(os.path.join(here, "*.py")) for n in names(f)]
    assert len(every) == len(set(every)) == sum(map(len, KERNEL_NAMES.values()))


@functools.lru_cache(maxsize=None)
def _imported_from_kernel_tier():
    """kernel module -> the names that modules of ``paddle_tpu/`` outside
    ``ops/pallas/`` import from it (one walk of the package for all cases)."""
    import ast

    here = _pallas_dir()
    out = {}
    for path in glob.glob(os.path.join(os.path.dirname(os.path.dirname(here)), "**", "*.py"),
                          recursive=True):
        if os.path.dirname(path) == here:
            continue
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.ImportFrom) and ".pallas." in "." + (node.module or ""):
                out.setdefault(node.module.rsplit(".", 1)[1], set()).update(
                    a.name for a in node.names)
    return out


@pytest.mark.parametrize("filename", sorted(KERNEL_NAMES))
def test_every_kernel_is_reached_from_outside_the_kernel_tier(filename):
    """A kernel nothing calls is a kernel nobody runs (ROADMAP D5): every
    ``pallas_call`` of a kernel file lies under a top-level function of that
    file which a module of ``paddle_tpu/`` outside ``ops/pallas/`` imports by
    name (``tests/test_environment_reads.py`` keeps that caller from hiding
    behind an environment switch)."""
    import ast

    here = _pallas_dir()
    stem = filename[:-3]
    tree = ast.parse(open(os.path.join(here, filename)).read())
    # what each top-level function names; ``f.defvjp(fwd, bwd)`` makes f name both
    uses = {}
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            uses.setdefault(node.name, set()).update(
                n.id for n in ast.walk(node) if isinstance(n, ast.Name))
        elif (isinstance(node, ast.Expr) and isinstance(node.value, ast.Call)
              and isinstance(node.value.func, ast.Attribute)
              and node.value.func.attr == "defvjp"):
            uses.setdefault(node.value.func.value.id, set()).update(
                a.id for a in node.value.args)

    def reaches(name, target, seen):
        if name == target:
            return True
        seen.add(name)
        return any(reaches(u, target, seen) for u in uses.get(name, set()) - seen if u in uses)

    imported = _imported_from_kernel_tier().get(stem, set())
    assert imported, f"nothing outside ops/pallas/ imports {filename}"
    for call in _pallas_calls(tree):
        kernel = {k.arg: k.value for k in call.keywords}["name"].value
        home = next(n.name for n in tree.body if isinstance(n, ast.FunctionDef)
                    and n.lineno <= call.lineno <= n.end_lineno)
        entries = sorted(f for f in imported if reaches(f, home, set()))
        assert entries, (f"{kernel} ({filename}:{call.lineno}, in {home}) is reached from "
                         f"nothing that is imported outside ops/pallas/: {sorted(imported)}")


def test_train_step_call_is_one_span_with_its_step_and_steps(host_spans):
    model = nn.Linear(8, 4)
    opt = P.optimizer.SGD(0.1, parameters=model.parameters())
    step = P.jit.TrainStep(model, lambda m, x, y: P.nn.functional.mse_loss(m(x), y), opt)
    x, y = P.randn([4, 8]), P.randn([4, 4])
    step(x, y)                                          # compiles, untraced
    with host_spans("train_step.") as spans:
        step(x, y)
        step.run_steps(P.randn([3, 4, 8]), P.randn([3, 4, 4]))
    assert [(name, stats) for name, _, _, stats in spans] == [
        ("train_step.call", {"step": 1, "steps": 1}), ("train_step.call", {"step": 2, "steps": 3})]
    assert opt._step_count == 5
