"""What the files of tests/aot share: programs compiled for a TPU v5e that is
described and not attached, an engine at a configuration's own geometry, its
programs lowered for the cell's own shapes and compiled ONCE a run, and the
one place that says which modules answer "on the chip" while they are traced.

A configuration's described-device programs live in ``test_<family>.py`` here,
lowered by ``compiled_program`` and steered by ``on_the_chip``: a
``model_config`` PR adds a file, not cases to another family's."""
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
V5E_BYTES_LIMIT = 16_909_336_064

# Under a described-device compile the default backend is still the CPU, so
# ``paddle_tpu.device.on_tpu()`` says no where the chip says yes.  Modules
# import the name, so it is set module by module: every module of
# ``paddle_tpu`` whose ``on_tpu()`` chooses a branch of a TRACED program
# (``device`` itself for ``nn/functional/flash_attention.py``'s call-time
# import) ...
STEERED = ("device", "ops.held_experts", "ops.latent_attention", "ops.paged_attention",
           "ops.pallas.flash_attention", "ops.pallas.fused_norm",
           "ops.pallas.fused_ops", "ops.pallas.int8_matmul")
# ... and those that hold the name and are left alone, each with its reason
LEFT_ALONE = {
    "ops.pallas.autotune": "its branch RUNS kernels on a device to time them",
    "nn.functional.flash_attention": "imports the name at call time: steered through device",
}


def on_the_chip(monkeypatch):
    """``on_tpu`` answers yes in every module of ``STEERED`` until the test
    ends."""
    for name in STEERED:
        monkeypatch.setattr(importlib.import_module("paddle_tpu." + name),
                            "on_tpu", lambda: True)


def kernel_calls(text, name):
    """How many custom calls of a compiled program's text bear a kernel's
    ``pallas_call(name=)``: a device trace names the kernel's event by it, and
    the benchmark's kernel metrics match that name."""
    return len(re.findall(rf"%{name}(\.\d+)? = [^\n]* custom-call\(", text))


def compile_kernel(fn, chip, *shapes, names=(), donate=()):
    """``fn`` compiled for ``chip`` at ``shapes`` ((shape, dtype) each, those
    of ``donate`` donated); ``names``: the kernels the program must call."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert kernel_calls(text, name), name
    return compiled


def engine_of(config_file):
    """``(cfg, ServingEngine)`` over a configuration's model at its own
    geometry with a pool of two blocks, built under ``jax.eval_shape``: its
    weights, rope, pools and state a slot are shapes and types (those
    ``make_weights`` gives, through the family's own ``assign``) and no
    bytes.  The programs take all of them as arguments, and
    ``compiled_program`` lowers them for the cell's own shapes.  (As 5-10 GB
    of zeros an engine took 12 s to build alone and 105 s beside three
    others: PR 40.)"""
    from benchmark.harness import loader
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.inference import ServingEngine

    set_hybrid_communicate_group(None)
    with open(os.path.join(loader.ROOT, config_file)) as f:
        cfg = json.load(f)
    family = loader.load_module("families", cfg["family"])
    model = family.build_model(cfg)
    held = ("_weights", "_rope", "caches", "slot_state")    # an engine's device arrays
    built = []

    def build(weights):
        family.assign(model, weights)
        built.append(ServingEngine(model, **dict(cfg["engine"], num_blocks=2)))
        return tuple(getattr(built[0], name) for name in held)

    arrays = jax.eval_shape(build, jax.eval_shape(lambda: family.make_weights(cfg, 0)))
    for name, shapes in zip(held, arrays):
        setattr(built[0], name, shapes)
    for name, value in vars(built[0]).items():      # a device array ``held`` lacks
        assert not any(isinstance(leaf, jax.core.Tracer)
                       for leaf in jax.tree_util.tree_leaves(value)), name
    return cfg, built[0]


def compiled_program(eng, cfg, kind, chip):
    """THE compiled program ``kind`` of an engine (``step_prefill_T<t>`` and
    ``step_decode``: the step at a prefill's and a decode's ``mq``;
    ``mixed_K<k>``, ``mega_K<k>``: the scans) for the cell's own shapes: pools
    at the configuration's ``num_blocks``, state a slot where the engine has
    it, the ONE control block a launch sends up (ISSUE 35).  Kept on the
    engine, so a program is compiled once a run however many tests read it;
    every compile prints one line (``pytest -s`` counts them)."""
    from paddle_tpu.inference.serving import control_layout

    kept = vars(eng).setdefault("compiled_for_v5e", {})
    if kind in kept:
        return kept[kind]
    B, T, P, C = eng.B, eng.T, eng.P, eng.pc
    nb = cfg["engine"]["num_blocks"]

    def sds(a, shape=None):
        return jax.ShapeDtypeStruct(tuple(shape or a.shape), a.dtype, sharding=chip)

    def block(launch, n=0):
        return jax.ShapeDtypeStruct((control_layout(launch, B, P, n, len(eng.kinds)).size,),
                                    jnp.int32, sharding=chip)

    if eng.cache_spec.stacked:          # one array [cache layers, blocks, ...]
        pools = tuple(sds(a, a.shape[:1] + (nb,) + a.shape[2:]) for a in eng.caches)
    else:                               # a pool a KIND of cache layer, each of its own size
        sizes = [nb[kind.name] if isinstance(nb, dict) else nb
                 for kind in eng.kinds for _ in range(kind.layers)]
        pools = tuple([sds(a, (n,) + a.shape[1:]) for a, n in zip(layers, sizes)]
                      for layers in eng.caches)
    head = (jax.tree_util.tree_map(sds, eng._weights),
            pools + tuple(sds(s) for s in eng.slot_state), sds(eng._rope))
    steps = {f"step_prefill_T{T}": (T, T), "step_decode": (B, 1)}      # kind: (tokens, mq)
    if kind in steps:
        n, mq = steps[kind]
        lowered = eng._build_step().lower(*head, block("step", n), mq=mq)
    else:
        scan, _, k = kind.partition("_K")
        build, n = {"mixed": (eng._build_mixed_megastep, int(k) * C),
                    "mega": (eng._build_megastep, 0)}[scan]
        lowered = build().lower(*head, block(scan, n), K=int(k))
    kept[kind] = compiled = lowered.compile()
    mem = compiled.memory_analysis()
    print("compiled", cfg["family"], kind, dict(
        arguments=mem.argument_size_in_bytes, temporaries=mem.temp_size_in_bytes,
        live=live_bytes(mem)))
    return compiled


def live_bytes(mem):
    """What a program holds of the chip while it runs."""
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def fits_as_the_file_says(cfg, kind, compiled, margin, live_now=None):
    """The tail every family's cases share: the program leaves ``margin``
    bytes of the chip free, and its arguments and live bytes are the
    configuration file's ``memory.compiled_for_v5e``; ``live_now`` where a PR
    that could not edit the file took bytes out of the program: the live
    bytes it compiles to since, no more than the file still says. Returns
    (mem, live, the file's figures)."""
    mem = compiled.memory_analysis()
    live = live_bytes(mem)
    assert live < V5E_BYTES_LIMIT - margin, live
    said = cfg["memory"].get("compiled_for_v5e", {}).get(kind)
    assert said is not None, "the configuration's memory.compiled_for_v5e lacks " + kind
    # the files' ``arguments`` were compiled when a launch took its control rows
    # as eleven to sixteen arrays, each padded to a tile of its own; since
    # ISSUE 35 they are ONE block, 11-20 KB less of 11-15 GB (the files are the
    # benchmark's, which that PR could not edit)
    assert 0 <= said["arguments"] - mem.argument_size_in_bytes < 32 * 1024
    want = said["live"] if live_now is None else live_now
    assert want <= said["live"] and abs(want / live - 1) < 0.01, (said["live"], want, live)
    return mem, live, said
