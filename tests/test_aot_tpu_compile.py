"""The Pallas kernels of the main paths, compiled at real widths for a TPU
v5e that is described and not attached (libtpu's compiler is installed
wherever jax[tpu] is). Interpret mode cannot see what this sees: a block
that does not fit VMEM, a slice the tiling refuses. Nothing runs, so this
says nothing about results or speed, and a pass here is not a chip run.

Named to sort first: tier-1 is cut by its clock, and a file late in the
alphabet guards nothing.
"""
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile against
        pytest.skip(f"cannot describe a TPU v5e topology: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-device executable is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, chip, *shapes, names=()):
    """``names``: the kernels' ``pallas_call(name=)``, which the compiled
    program must give its custom calls: a device trace names the kernel's
    event by it, and the benchmark's kernel metrics match that name."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert re.search(rf"%{name}(\.\d+)? = [^\n]* custom-call\(", text), name
    return compiled


BF16 = jnp.bfloat16


# (bh, kv_rep, seq, causal); "cell" is mistral7b.train.pretrain-2k's call
# (4 x 32 heads over 8 KV heads), "ring" the non-causal block that ring
# attention runs off the diagonal
_FLASH_CALLS = {
    "mha-2048": (32, 1, 2048, True), "gqa4-2048": (32, 4, 2048, True),
    "mha-4096": (32, 1, 4096, True), "gqa4-4096": (32, 4, 4096, True),
    "cell": (128, 4, 2048, True), "ring": (32, 4, 2048, False),
}


@pytest.mark.parametrize("call", _FLASH_CALLS)
@pytest.mark.parametrize("kind", ["fwd", "bwd"])
def test_flash_attention(chip, kind, call):
    """bf16 operands at the table's blocks: whether Mosaic takes the kernels'
    bf16 products (the backward's transposed scores among them) and their
    tiles fit VMEM is learned here, on the CPU."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas.autotune import get_flash_blocks

    bh, kv_rep, seq, causal = _FLASH_CALLS[call]
    d = 128
    scale = d ** -0.5
    bq, bk = get_flash_blocks(kind, seq, seq, d)
    q = ((bh, seq, d), BF16)
    kv = ((bh // kv_rep, seq, d), BF16)
    if kind == "fwd":
        _compile(lambda q, k, v: fa._pallas_fwd(
            q, k, v, causal, scale, bq, bk, False, kv_rep=kv_rep),
            chip, q, kv, kv, names=("flash_fwd",))
    else:
        _compile(lambda q, k, v, o, lse, g: fa._pallas_bwd(
            q, k, v, o, lse, g, causal, scale, bq, bk, False, kv_rep=kv_rep),
            chip, q, kv, kv, q, ((bh, seq), jnp.float32), q,
            names=("flash_bwd_dq", "flash_bwd_dkv"))


@pytest.mark.parametrize("rows", [16384, 2048])
def test_rms_norm(chip, rows):
    from paddle_tpu.ops.pallas import fused_norm as fn

    x = ((rows, 4096), BF16)
    _compile(lambda x, w: fn._pallas_rms(x, w, 1e-6, False),
             chip, x, ((4096,), BF16))


@pytest.mark.parametrize("hidden,dtype", [(4096, BF16), (2560, BF16),
                                          (8192, BF16), (4096, jnp.float32)])
def test_rms_norm_residual(chip, hidden, dtype):
    """[16384, 4096] bf16 with the old fixed 256-row block asked for 16.01M
    of a 16.00M scoped VMEM limit."""
    from paddle_tpu.ops.pallas import fused_norm as fn

    x = ((16384, hidden), dtype)
    _compile(lambda x, r, w: fn._pallas_rms_residual(x, r, w, 1e-6, False),
             chip, x, x, ((hidden,), dtype))


@pytest.mark.parametrize("m", [8, 512])
def test_int8_matmul(chip, m, monkeypatch):
    from paddle_tpu.ops.pallas import int8_matmul as im

    # under a described-device compile the default backend is still the CPU,
    # where _int8_mm_impl takes its jnp branch: steer it to the kernel here
    monkeypatch.setattr(im, "on_tpu", lambda: True)
    _compile(lambda x, q, s: im._int8_mm_impl(x, q, s, False), chip,
             ((m, 4096), BF16), ((4096, 11008), jnp.int8),
             ((11008,), jnp.float32))


@pytest.mark.parametrize("mq", [1, 64, 256], ids=["decode", "chunk64", "prefill256"])
def test_paged_attention_keeps_one_pool_and_no_whole_table(chip, mq, monkeypatch):
    """The dense paged attention at the benchmark's serving geometry
    (mistral-7b-v0.3.serve1: 32 rows, tables of 40 blocks of 64, 8 kv heads
    of 128, a pool of 1024 blocks), two iterations in a scan with the pool
    in the carry as the engine's scans hold it, compiled as the chip will
    run it: whether a row feeds one token is data, so every one of the three
    holds the one-token rows' ``paged_decode`` kernel (the chunk rows' pass
    is XLA) and, since ISSUE 31, the cache write's ``paged_write`` kernel,
    which takes the pool where it lies and returns it. What the chip's
    compiler must not do is what it did before
    ISSUE 27: keep a second copy of the pool in another layout (the write,
    the gather and the kernel's operand must agree on one, or 268 MB a layer
    are copied in and out), or build anything as large as every row's whole
    table (168 MB in bf16)."""
    from paddle_tpu.ops import paged_attention as pa

    # a described-device compile still sees the CPU as the default backend
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    blha_attention = pa.blha_attention.__wrapped__     # no trace made for the CPU
    B, P, bs, H, KV, D, nb = 32, 40, 64, 32, 8, 128, 1024
    T = B if mq == 1 else 256

    def two_iterations(qkv, kc, vc, dec, now, cu, bt, rope):
        def body(carry, _):
            kc, vc = carry
            out = blha_attention(
                qkv, kc, vc, jnp.zeros_like(dec), dec, now, cu, bt, num_heads=H,
                kv_num_heads=KV, head_dim=D, block_size=bs, max_q_len=mq,
                use_neox_style=True, compute_dtype=BF16, rope_emb=rope)
            return (out[1], out[2]), out[0]
        return jax.lax.scan(body, (kc, vc), None, length=2)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool = sds((nb, KV, bs, D), BF16)
    i32 = jnp.int32
    compiled = jax.jit(two_iterations, donate_argnums=(1, 2)).lower(
        sds((T, (H + 2 * KV) * D), BF16), pool, pool, sds((B,), i32), sds((B,), i32),
        sds((B + 1,), i32), sds((B, P), i32),
        sds((2, 1, P * bs, 1, D // 2), jnp.float32)).compile()
    text = compiled.as_text()
    assert re.search(r"%paged_decode(\.\d+)? = [^\n]* custom-call\(", text)
    assert re.search(r"%paged_write(\.\d+)? = [^\n]* custom-call\(", text)
    assert "kv_write/scatter" not in text
    pool_bytes = nb * KV * bs * D * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes // 2, f"{temp / 1e6:.0f} MB of temporaries"
    # one layout of the pool, the argument's row-major order, on the
    # parameter, the write, the kernel's operand and the gather alike
    orders = set(re.findall(r"bf16\[1024,8,64,128\]\{([0-9,]+)", text))
    assert orders == {"3,2,1,0"}, orders
    assert not re.search(r"= bf16\[1024,8,64,128\][^\n]* copy\(", text)
    whole = B * KV * P * bs * D
    views_of_the_pool = {(nb, KV, bs, D), (nb * KV, bs, D), (nb * KV * bs, D)}
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", text)}
    big = [s for s in shapes - views_of_the_pool if math.prod(s) >= whole]
    assert not big, big


# ------------------------------------------- a looped model's three programs
OURO = "benchmark/configs/ouro-2.6b.serve1.json"
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def ouro_engine():
    """``ServingEngine`` over Ouro-2.6B at ouro-2.6b.serve1's geometry, its
    weights zeros and its pool two blocks: the programs take both as
    arguments, and are lowered below for the cell's own shapes."""
    import json

    from benchmark.harness import loader
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.inference import ServingEngine

    set_hybrid_communicate_group(None)
    cfg = json.load(open(os.path.join(loader.ROOT, OURO)))
    family = loader.load_module("families", cfg["family"])
    model = family.build_model(cfg)
    for p in jax.tree_util.tree_leaves(family.params_of(model),
                                       is_leaf=lambda x: hasattr(x, "_value")):
        p._value = jnp.zeros(tuple(p.shape), BF16)
    return cfg, ServingEngine(model, **dict(cfg["engine"], num_blocks=2))


def _one_control_block_less(said, compiled):
    """The configuration files' ``arguments`` were compiled when a launch took
    its control rows as eleven to sixteen arrays, each padded to a tile of its
    own; since ISSUE 35 they are ONE block, 11-20 KB less of 11-15 GB (the
    files are the benchmark's, which that PR could not edit)."""
    return 0 <= said - compiled < 32 * 1024


@pytest.mark.parametrize("kind", ["step_prefill_T256", "mixed_K8", "mega_K8"])
def test_a_looped_models_programs_keep_one_pool_in_place(chip, ouro_engine, kind, monkeypatch):
    """The prefill step, the mixed scan and the decode scan of Ouro-2.6B
    whole (48 layers' weights stacked, four passes, a pool of 192 cache
    layers x 6,144 tokens: 9.66 GB) compiled as the chip will run them. Both
    loops are loops of the program: ONE ``paged_decode`` call and ONE
    ``paged_write`` call in its text, and no scatter under ``kv_write``.
    The pool is written and read in place at a traced layer index: one
    layout of it (its stacked form and the same bytes seen as layers x blocks),
    no copy, no temporary the size of one cache layer; the stacked weights
    are not copied either (held as three matrices, q, k and v were: 1.21 GB).
    And the figures that sized the pool: a GB and more free under each."""
    from paddle_tpu.inference.serving import control_layout
    from paddle_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    cfg, eng = ouro_engine
    B, T, P, K, C = eng.B, eng.T, eng.P, eng.megastep_k, eng.pc
    nb = cfg["engine"]["num_blocks"]
    assert (T, K) == (256, 8)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def block(kind, n=0):        # the ONE control array a launch sends up (ISSUE 35)
        return sds((control_layout(kind, B, P, n).size,), jnp.int32)

    weights = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), eng._weights)
    caches = tuple(sds((a.shape[0], nb) + a.shape[2:], a.dtype) for a in eng.caches)
    head = (weights, caches, sds(eng._rope.shape, eng._rope.dtype))
    lowered = {
        "step_prefill_T256": lambda: eng._build_step().lower(*head, block("step", T), mq=T),
        "mixed_K8": lambda: eng._build_mixed_megastep().lower(
            *head, block("mixed", K * C), K=K),
        "mega_K8": lambda: eng._build_megastep().lower(*head, block("mega"), K=K),
    }[kind]()
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r"%paged_decode(\.\d+)? = [^\n]* custom-call\(", text)) == 1
    assert len(re.findall(r"%paged_write(\.\d+)? = [^\n]* custom-call\(", text)) == 1
    assert "kv_write/scatter" not in text
    pool = caches[0].shape
    assert pool == (192, nb, 16, eng.bs, 128) and nb * eng.bs == 6144
    stacked = ",".join(map(str, pool))
    flat = ",".join(map(str, (pool[0] * pool[1],) + pool[2:]))
    orders = set(re.findall(rf"bf16\[(?:{stacked}|{flat})\]\{{([0-9,]+)", text))
    assert orders == {"4,3,2,1,0", "3,2,1,0"}, orders
    assert not re.search(rf"= bf16\[(?:{stacked}|{flat})\][^\n]* copy\(", text)
    assert not re.search(r"= bf16\[48,[0-9,]+\][^\n]* copy\(", text)
    mem = compiled.memory_analysis()
    one_cache_layer = math.prod(pool[1:]) * 2
    assert mem.temp_size_in_bytes < one_cache_layer, mem.temp_size_in_bytes
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert live < V5E_BYTES_LIMIT - 10 ** 9
    said = cfg["memory"]["compiled_for_v5e"][kind]
    assert _one_control_block_less(said["arguments"], mem.argument_size_in_bytes)
    assert abs(said["live"] / live - 1) < 0.01 and said["temporaries"] < one_cache_layer


# ------------------- latent attention under a learned selection, three programs
DSA = "benchmark/configs/deepseek-v3.2-exp.serve1.json"


def _engine_of(config_file):
    """``ServingEngine`` over a configuration's model at its own geometry, its
    weights zeros (in the shapes and types ``make_weights`` gives them) and its
    pool two blocks: the programs take both as arguments, and are lowered for
    the cell's own shapes."""
    import json

    from benchmark.harness import loader
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.inference import ServingEngine

    set_hybrid_communicate_group(None)
    cfg = json.load(open(os.path.join(loader.ROOT, config_file)))
    family = loader.load_module("families", cfg["family"])
    model = family.build_model(cfg)
    shapes = jax.eval_shape(lambda: family.make_weights(cfg, 0))
    jax.tree_util.tree_map(
        lambda p, s: setattr(p, "_value", jnp.zeros(s.shape, s.dtype)),
        family.params_of(model), shapes, is_leaf=lambda x: hasattr(x, "_value"))
    return cfg, ServingEngine(model, **dict(cfg["engine"], num_blocks=2))


@pytest.fixture(scope="module")
def dsa_engine():
    """DeepSeek-V3.2-Exp at deepseek-v3.2-exp.serve1's geometry."""
    return _engine_of(DSA)


@pytest.mark.parametrize("kind", ["step_prefill_T512", "mixed_K8", "mega_K8"])
def test_a_selected_latent_models_programs_fit_the_chip(chip, dsa_engine, kind):
    """The prefill step, the mixed scan and the decode scan of
    deepseek-v3.2-exp.serve1 (4.6 B parameters, a pool of 5,120 blocks x two
    arrays x five layers) compiled as the chip will run them: both pool
    arrays keep the argument's row-major layout and neither is copied (at the
    latent's own width 576 the compiler transposed every layer's pool: PR 26),
    the score buffer and the selection are temporaries, and the largest
    program leaves 1.5 GB of the chip free.  The figures are the
    configuration file's ``memory.compiled_for_v5e``."""
    from paddle_tpu.inference.serving import control_layout

    cfg, eng = dsa_engine
    B, T, P, K, C = eng.B, eng.T, eng.P, eng.megastep_k, eng.pc
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (B, T, P, K, C) == (24, 512, 268, 8, 64)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def block(kind, n=0):        # the ONE control array a launch sends up (ISSUE 35)
        return sds((control_layout(kind, B, P, n).size,), jnp.int32)

    weights = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), eng._weights)
    caches = tuple([sds((nb,) + a.shape[1:], a.dtype) for a in layers]
                   for layers in eng.caches)
    assert [c[0].shape for c in caches] == [(nb, bs, 640), (nb, bs, 128)]
    head = (weights, caches, sds(eng._rope.shape, eng._rope.dtype))
    compiled = {
        "step_prefill_T512": lambda: eng._build_step().lower(*head, block("step", T), mq=T),
        "mixed_K8": lambda: eng._build_mixed_megastep().lower(
            *head, block("mixed", K * C), K=K),
        "mega_K8": lambda: eng._build_megastep().lower(*head, block("mega"), K=K),
    }[kind]().compile()
    text = compiled.as_text()
    for width in (640, 128):
        pool = f"{nb},{bs},{width}"
        orders = set(re.findall(rf"bf16\[{pool}\]\{{([0-9,]+)", text))
        assert orders == {"2,1,0"}, (width, orders)
        assert not re.search(rf"= bf16\[{pool}\][^\n]* copy\(", text)
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(kind, dict(arguments=mem.argument_size_in_bytes, temporaries=mem.temp_size_in_bytes,
                     live=live))
    assert live < V5E_BYTES_LIMIT - 1.5e9, live
    said = cfg["memory"].get("compiled_for_v5e", {}).get(kind)
    assert said is not None, "the configuration's memory.compiled_for_v5e lacks " + kind
    assert _one_control_block_less(said["arguments"], mem.argument_size_in_bytes)
    assert abs(said["live"] / live - 1) < 0.01


# ------------------- conv layers with state a slot, GQA at heads of 64, 64 experts
LFM2 = "benchmark/configs/lfm2-24b-a2b.serve1.json"
LFM2_PROGRAMS = ("step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4",
                 "mega_K8")


@pytest.fixture(scope="module")
def lfm2_engine():
    """LFM2-24B-A2B's first ten layers at lfm2-24b-a2b.serve1's geometry."""
    return _engine_of(LFM2)


@pytest.mark.parametrize("kind", LFM2_PROGRAMS)
def test_a_conv_state_models_programs_fit_the_chip(chip, lfm2_engine, kind):
    """The six programs lfm2-24b.serve.chat-batch can reach (the step at a
    prefill's and at a decode's ``mq``, the decode scan at K 2, 4 and 8, the
    mixed scan) of lfm2-24b-a2b.serve1 (5.27 B parameters with every expert of
    eight layers, a pool of 2,048 blocks x keys and values x two attention
    layers, conv state ``[8, 128, 2, 2048]`` a slot) compiled as the chip will
    run them: heads of 64 take the XLA attention and the scatter (no
    ``paged_decode`` / ``paged_write`` custom call); the state a slot is
    donated and updated in place, never copied whole but ONCE in the decode
    scan's body (8.4 MB: what a row the scan has frozen keeps); a pool array
    ``[2048, 8, 64, 64]``, whose last axis is half a lane tile, gets a device
    layout of the compiler's own and is copied ONCE into the layout the
    scatter and the gather want and once back, outside the scan's loop (8
    copies of 134 MB a launch, 0.5 GB of temporaries: ROADMAP's speed item for
    heads of 64; one more copy would be a copy an iteration); and the largest
    program leaves 1.5 GB of the chip free.  The figures are the configuration
    file's ``memory.compiled_for_v5e``."""
    from paddle_tpu.inference.serving import control_layout

    cfg, eng = lfm2_engine
    B, T, P, K, C = eng.B, eng.T, eng.P, eng.megastep_k, eng.pc
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (B, T, P, K, C) == (128, 512, 44, 8, 64)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def block(kind, n=0):        # the ONE control array a launch sends up (ISSUE 35)
        return sds((control_layout(kind, B, P, n).size,), jnp.int32)

    weights = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), eng._weights)
    pools = tuple([sds((nb,) + a.shape[1:], a.dtype) for a in layers]
                  for layers in eng.caches)
    assert [[a.shape for a in c] for c in pools] == [[(nb, 8, bs, 64)] * 2] * 2
    (state,) = eng.slot_state
    assert state.shape == (8, 128, 2, 2048) and state.dtype == jnp.bfloat16
    head = (weights, pools + (sds(state.shape, state.dtype),),
            sds(eng._rope.shape, eng._rope.dtype))
    scans = {f"mega_K{k}": (lambda k=k: eng._build_megastep().lower(
        *head, block("mega"), K=k)) for k in (2, 4, 8)}
    compiled = dict(scans, **{
        "step_prefill_T512": lambda: eng._build_step().lower(*head, block("step", T), mq=T),
        "step_decode": lambda: eng._build_step().lower(*head, block("step", B), mq=1),
        "mixed_K8": lambda: eng._build_mixed_megastep().lower(
            *head, block("mixed", K * C), K=K),
    })[kind]().compile()
    text = compiled.as_text()
    assert "paged_decode" not in text and "paged_write" not in text
    state_copies = len(re.findall(r"= bf16\[8,128,2,2048\][^\n]* copy\(", text))
    assert state_copies == (1 if kind.startswith("mega") else 0)
    assert len(re.findall(rf"= bf16\[{nb},8,{bs},64\][^\n]* copy\(", text)) <= 8
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(kind, dict(arguments=mem.argument_size_in_bytes, temporaries=mem.temp_size_in_bytes,
                     live=live))
    assert 0.25 * V5E_BYTES_LIMIT < live < V5E_BYTES_LIMIT - 1.5e9, live
    said = cfg["memory"].get("compiled_for_v5e", {}).get(kind)
    assert said is not None, "the configuration's memory.compiled_for_v5e lacks " + kind
    assert said["arguments"] == mem.argument_size_in_bytes
    assert abs(said["live"] / live - 1) < 0.01


# ------------------- the expert layer as three grouped products (ISSUE 39)
PANGU = "benchmark/configs/openpangu-ultra-moe-718b.serve1.json"
# (configuration, held experts, E, F, k, the mixed scan's live bytes before: PR 38's
# 12.99 GB for LFM2, and for openPangu the largest program PR 26 compiled, 13.22 GB)
EXPERT_LAYERS = {"lfm2": (LFM2, 64, 2048, 1536, 4, 12_986_028_032),
                 "openpangu": (PANGU, 16, 7680, 2048, 8, 13_223_340_544)}


@pytest.fixture(scope="module")
def expert_engines():
    """An engine a configuration, built once (the LFM2 one is ``lfm2_engine``'s twin:
    the fixture above hands its own to tests that must not see the steering)."""
    return {}


@pytest.mark.parametrize("family", sorted(EXPERT_LAYERS))
def test_the_expert_layer_is_three_grouped_products(chip, family, expert_engines,
                                                    monkeypatch):
    """With the platform answering yes, ``held_experts`` at the cell's widths
    (512 packed tokens, bf16) compiles to ONE ``expert_gmm`` call an expert
    matrix (gate, up, down) and no tile loop; the stacks ``eg`` / ``eu`` /
    ``ed`` stay where they lie: no copy of one, nor of an expert's slice,
    among the temporaries; each call's blocks fit the kernel's VMEM limit
    (the compile refuses otherwise).  And the mixed scan, the largest program
    of the window, is no larger than it was with the loop."""
    from paddle_tpu.inference.serving import control_layout
    from paddle_tpu.models import pangu_moe
    from paddle_tpu.ops.pallas import expert_gmm

    config_file, n_held, E, F, k, live_before = EXPERT_LAYERS[family]
    monkeypatch.setattr(pangu_moe, "on_tpu", lambda: True)
    T = 512

    def layer(x, idx, w, eg, eu, ed, valid):
        counts = {"expert_rows_grouped": jnp.zeros((), jnp.int32)}
        y, picks = pangu_moe.held_experts(x, idx, w, eg, eu, ed, 0, valid, counts=counts)
        return y, picks, counts

    compiled = _compile(layer, chip, ((T, E), BF16), ((T, k), jnp.int32),
                        ((T, k), jnp.float32), ((n_held, E, F), BF16),
                        ((n_held, E, F), BF16), ((n_held, F, E), BF16), ((T,), jnp.bool_),
                        names=("expert_gmm",))
    text = compiled.as_text()
    assert len(re.findall(r"%expert_gmm(\.\d+)? = [^\n]* custom-call\(", text)) == 3
    stacks = "|".join((f"{n_held},{E},{F}", f"{n_held},{F},{E}", f"{E},{F}", f"{F},{E}"))
    made = [line.strip()[:120] for line in text.splitlines()
            if re.search(rf"= bf16\[({stacks})\]", line)
            and not re.search(r"\] (parameter|get-tuple-element)\(", line)
            and " parameter(" not in line and " get-tuple-element(" not in line]
    assert not made, made           # a loop's carry hands the stacks on; nothing makes one
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * pangu_moe._CHUNK_BYTES * 4
    assert expert_gmm.VMEM_LIMIT <= 100 << 20       # of a v5e core's 128 MiB

    if family not in expert_engines:
        expert_engines[family] = _engine_of(config_file)
    cfg, eng = expert_engines[family]
    B, P, K, C = eng.B, eng.P, eng.megastep_k, eng.pc
    nb = cfg["engine"]["num_blocks"]

    def sds(a, lead=None):
        shape = a.shape if lead is None else (lead,) + a.shape[1:]
        return jax.ShapeDtypeStruct(tuple(shape), a.dtype, sharding=chip)

    weights = jax.tree_util.tree_map(sds, eng._weights)
    caches = tuple([sds(a, nb) for a in layers] for layers in eng.caches) + tuple(
        sds(s) for s in eng.slot_state)
    block = jax.ShapeDtypeStruct((control_layout("mixed", B, P, K * C).size,), jnp.int32,
                                 sharding=chip)
    mixed = eng._build_mixed_megastep().lower(weights, caches, sds(eng._rope), block,
                                              K=K).compile()
    sparse = sum("router" in lw for lw in eng._weights["layers"])
    assert len(re.findall(r"%expert_gmm(\.\d+)? = [^\n]* custom-call\(",
                          mixed.as_text())) == 3 * sparse
    mem = mixed.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(family, "mixed_K8 steered", dict(temporaries=mem.temp_size_in_bytes, live=live))
    assert live <= live_before, (live, live_before)
