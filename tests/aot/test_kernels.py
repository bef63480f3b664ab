"""The Pallas kernels of the main paths, each alone at the widths its callers
give it."""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from described_device import (BF16, LEFT_ALONE, STEERED, compile_kernel, kernel_calls,
                              on_the_chip)


def test_every_module_that_asks_on_tpu_is_steered_or_left_alone_with_a_reason():
    """A module of ``paddle_tpu`` that starts asking ``on_tpu()`` cannot be
    forgotten: it is in ``STEERED`` or in ``LEFT_ALONE``, and nothing is
    listed that no longer asks."""
    import pathlib

    import paddle_tpu

    root = pathlib.Path(paddle_tpu.__file__).parent
    asking = {".".join(f.relative_to(root).with_suffix("").parts).removesuffix(".__init__")
              for f in root.rglob("*.py") if re.search(r"\bon_tpu\b", f.read_text())}
    assert not set(STEERED) & set(LEFT_ALONE)
    assert asking == set(STEERED) | set(LEFT_ALONE)


def test_no_model_file_chooses_a_kernel():
    """Which implementation of an op a call takes is the op's to decide
    (``paddle_tpu/ops/``): no file under ``paddle_tpu/models/`` imports or
    names ``on_tpu`` or a gate (``*_in_kernel``).  Read off the syntax trees: a
    docstring may still say which gate an op asks."""
    import ast
    import pathlib

    import paddle_tpu

    def chooses(name):
        return name == "on_tpu" or name.endswith("_in_kernel")

    found = []
    for f in sorted((pathlib.Path(paddle_tpu.__file__).parent / "models").rglob("*.py")):
        for node in ast.walk(ast.parse(f.read_text())):
            names = ([a.name for a in node.names] if isinstance(node, ast.ImportFrom)
                     else [node.id] if isinstance(node, ast.Name)
                     else [node.attr] if isinstance(node, ast.Attribute) else [])
            found += [(f.name, node.lineno, n) for n in names if chooses(n)]
    assert not found, found


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
        compile_kernel(lambda q, k, v: fa._pallas_fwd(
            q, k, v, causal, scale, bq, bk, False, kv_rep=kv_rep),
            chip, q, kv, kv, names=("flash_fwd",))
    else:
        compile_kernel(lambda q, k, v, o, lse, g: fa._pallas_bwd(
            q, k, v, o, lse, g, causal, scale, bq, bk, False, kv_rep=kv_rep),
            chip, q, kv, kv, q, ((bh, seq), jnp.float32), q,
            names=("flash_bwd_dq", "flash_bwd_dkv"))


@pytest.mark.parametrize("rows", [16384, 2048])
def test_rms_norm(chip, rows):
    from paddle_tpu.ops.pallas import fused_norm as fn

    x = ((rows, 4096), BF16)
    compile_kernel(lambda x, w: fn._pallas_rms(x, w, 1e-6, False),
                   chip, x, ((4096,), BF16))


@pytest.mark.parametrize("hidden,dtype", [(4096, BF16), (2560, BF16),
                                          (8192, BF16), (4096, jnp.float32)])
def test_rms_norm_residual(chip, hidden, dtype):
    """[16384, 4096] bf16 with the old fixed 256-row block asked for 16.01M
    of a 16.00M scoped VMEM limit."""
    from paddle_tpu.ops.pallas import fused_norm as fn

    x = ((16384, hidden), dtype)
    compile_kernel(lambda x, r, w: fn._pallas_rms_residual(x, r, w, 1e-6, False),
                   chip, x, x, ((hidden,), dtype))


# mistral7b.train.pretrain-2k's calls: 4 x 2,048 tokens, 32 heads over 8 KV
# heads of 128, an intermediate width of 14,336
_TRAIN_Q, _TRAIN_K, _TRAIN_ACT = (4, 2048, 32, 128), (4, 2048, 8, 128), (8192, 14336)


@pytest.mark.parametrize("kernel", ["fused_rope", "swiglu_fwd", "swiglu_bwd"])
def test_the_training_forward_rotation_and_swiglu(chip, kernel, monkeypatch):
    """The three kernels of ``ops/pallas/fused_ops.py`` alone at the train
    cell's shapes in bf16, reached as the Llama trunk reaches them (``rope_fused``,
    ``swiglu_fused`` and its backward, the platform answering yes): the
    predicate takes the kernel at these dims, Mosaic takes its blocks, and
    nothing is copied around it (no temporaries: one read of each input, one
    write of each output)."""
    from paddle_tpu.ops.pallas import fused_ops as fo

    on_the_chip(monkeypatch)
    table = ((_TRAIN_Q[1], _TRAIN_Q[3] // 2), jnp.float32)
    act = (_TRAIN_ACT, BF16)
    if kernel == "fused_rope":
        compiled = compile_kernel(fo.rope_fused, chip, (_TRAIN_Q, BF16), (_TRAIN_K, BF16),
                                  table, table, names=(kernel,))
    elif kernel == "swiglu_fwd":
        compiled = compile_kernel(fo.swiglu_fused, chip, act, act, names=(kernel,))
    else:
        def loss(a, b, g):      # under the trunk's scope, which names the event
            with jax.named_scope("mlp"):
                return jnp.sum(fo.swiglu_fused(a, b) * g)

        compiled = compile_kernel(jax.grad(loss, argnums=(0, 1)), chip, act, act, act,
                                  names=(kernel,))
    calls = {k: kernel_calls(compiled.as_text(), k)
             for k in ("fused_rope", "swiglu_fwd", "swiglu_bwd")}
    assert {k: n for k, n in calls.items() if n} == {kernel: 2 if kernel == "fused_rope" else 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("m", [8, 512])
def test_int8_matmul(chip, m, monkeypatch):
    from paddle_tpu.ops.pallas import int8_matmul as im

    on_the_chip(monkeypatch)        # on the CPU _int8_mm_impl takes its jnp branch
    compile_kernel(lambda x, q, s: im._int8_mm_impl(x, q, s, False), chip,
                   ((m, 4096), BF16), ((4096, 11008), jnp.int8), ((11008,), jnp.float32))


# ((B, P, bs, H, KV, D, nb) of mistral-7b-v0.3.serve1 and of lfm2-24b-a2b.serve1, T, mq)
_SERVE1 = (32, 40, 64, 32, 8, 128, 1024)
_PAGED_CALLS = {"decode": (_SERVE1, 32, 1), "chunk64": (_SERVE1, 256, 64),
                "prefill256": (_SERVE1, 256, 256),
                "heads64-chunk64": ((128, 44, 64, 32, 8, 64, 2048), 512, 64)}


@pytest.mark.parametrize("call", _PAGED_CALLS)
def test_paged_attention_keeps_one_pool_and_no_whole_table(chip, call, monkeypatch):
    """The dense paged attention at the benchmark's serving geometry
    (mistral-7b-v0.3.serve1: 32 rows, tables of 40 blocks of 64, 8 kv heads
    of 128, a pool of 1024 blocks), two iterations in a scan with the pool
    in the carry as the engine's scans hold it, compiled as the chip will
    run it: whether a row feeds one token is data, so every one of the three
    holds the one-token rows' ``paged_decode`` kernel, since ISSUE 31 the cache
    write's ``paged_write`` kernel, which takes the pool where it lies and
    returns it, and since ISSUE 46, where a row may feed more than one token,
    the chunk rows' ``paged_chunk`` kernel: ONE call an iteration, no loop over
    chunk rows or context blocks left under ``paged_attention`` (no
    ``kv_gather``), and a pass's float32 scores nowhere in the program. What the chip's
    compiler must not do is what it did before
    ISSUE 27: keep a second copy of the pool in another layout (the write,
    the gather and the kernel's operand must agree on one, or 268 MB a layer
    are copied in and out), or build anything as large as every row's whole
    table (168 MB in bf16).  ``heads64``: lfm2-24b-a2b.serve1's attention
    layer (128 rows, tables of 44, 8 kv heads of 64, 2,048 blocks) over the
    pool ``lane_packing`` gives it, two heads a lane tile (ISSUE 41): the same
    two kernels, one layout, no copy; a head a row of 64 lanes, the compiler
    kept that pool in two layouts and copied each array in and out."""
    from paddle_tpu.ops import paged_attention as pa

    on_the_chip(monkeypatch)
    blha_attention = pa.blha_attention.__wrapped__     # no trace made for the CPU
    (B, P, bs, H, KV, D, nb), T, mq = _PAGED_CALLS[call]
    shape = (nb,) + pa.lane_packing(KV, D)[1](bs)
    assert shape[-1] == 128

    def two_iterations(qkv, kc, vc, dec, now, cu, bt, rope):
        def body(carry, _):
            kc, vc = carry
            out = blha_attention(
                qkv, kc, vc, jnp.zeros_like(dec), dec, now, cu, bt, num_heads=H,
                kv_num_heads=KV, head_dim=D, block_size=bs, max_q_len=mq,
                use_neox_style=True, compute_dtype=BF16, rope_emb=rope)
            return (out[1], out[2]), out[0]
        return jax.lax.scan(body, (kc, vc), None, length=2)

    pool, i32 = (shape, BF16), jnp.int32
    compiled = compile_kernel(
        two_iterations, chip, ((T, (H + 2 * KV) * D), BF16), pool, pool, ((B,), i32),
        ((B,), i32), ((B + 1,), i32), ((B, P), i32),
        ((2, 1, P * bs, 1, D // 2), jnp.float32), names=("paged_decode", "paged_write"),
        donate=(1, 2))
    text = compiled.as_text()
    assert "kv_write/scatter" not in text
    assert kernel_calls(text, "paged_chunk") == int(mq > 1)
    assert "kv_gather" not in text and "paged_attention/while" not in text
    assert not re.search(r"f32\[\d+,\d+,512\]", text)          # [KV, g x S, Lc] scores
    pool_bytes = math.prod(shape) * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes // 2, f"{temp / 1e6:.0f} MB of temporaries"
    # one layout of the pool, the argument's row-major order, on the
    # parameter, the write, the kernel's operand and the gather alike
    dims = ",".join(map(str, shape))
    orders = set(re.findall(rf"bf16\[{dims}\]\{{([0-9,]+)", text))
    assert orders == {"3,2,1,0"}, orders
    assert not re.search(rf"= bf16\[{dims}\][^\n]* copy\(", text)
    whole = B * P * math.prod(shape[1:])
    views_of_the_pool = {shape, (shape[0] * shape[1],) + shape[2:],
                         (shape[0] * shape[1] * shape[2], shape[3])}
    shapes = {tuple(int(d) for d in made.split(","))
              for made in re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", text)}
    big = [s for s in shapes - views_of_the_pool if math.prod(s) >= whole]
    assert not big, big


# (held experts, E, F, k, routed experts) of lfm2-24b-a2b.serve1 and
# openpangu-ultra-moe-718b.serve1
EXPERT_LAYERS = {"lfm2": (64, 2048, 1536, 4, 64), "openpangu": (16, 7680, 2048, 8, 256)}


@pytest.mark.parametrize("family", sorted(EXPERT_LAYERS))
def test_the_expert_layer_is_three_grouped_products(chip, family, monkeypatch):
    """With the platform answering yes, ``held_experts`` at the cell's widths
    (512 packed tokens, bf16) compiles to ONE ``expert_gmm`` call an expert
    matrix (gate, up, down) and no tile loop; the stacks ``eg`` / ``eu`` /
    ``ed`` stay where they lie: no copy of one, nor of an expert's slice,
    among the temporaries; each call's blocks fit the kernel's VMEM limit
    (the compile refuses otherwise).  (That the mixed scan, the largest program
    of the window, is no larger than it was with the loop is a line of each
    family's own ``mixed_K8`` case.)"""
    from paddle_tpu.ops import held_experts as he
    from paddle_tpu.ops.pallas import expert_gmm

    n_held, E, F, k, routed = EXPERT_LAYERS[family]
    on_the_chip(monkeypatch)
    T = 512

    def layer(x, idx, w, eg, eu, ed, valid):
        counts = {"expert_rows_grouped": jnp.zeros((), jnp.int32)}
        y, picks = he.held_experts(x, idx, w, eg, eu, ed, 0, valid, counts=counts,
                                   routed=routed)
        return y, picks, counts

    compiled = compile_kernel(
        layer, chip, ((T, E), BF16), ((T, k), jnp.int32), ((T, k), jnp.float32),
        ((n_held, E, F), BF16), ((n_held, E, F), BF16), ((n_held, F, E), BF16),
        ((T,), jnp.bool_), names=("expert_gmm",))
    text = compiled.as_text()
    assert kernel_calls(text, "expert_gmm") == 3
    stacks = "|".join((f"{n_held},{E},{F}", f"{n_held},{F},{E}", f"{E},{F}", f"{F},{E}"))
    made = [line.strip()[:120] for line in text.splitlines()
            if re.search(rf"= bf16\[({stacks})\]", line)
            and not re.search(r"\] (parameter|get-tuple-element)\(", line)
            and " parameter(" not in line and " get-tuple-element(" not in line]
    assert not made, made           # a loop's carry hands the stacks on; nothing makes one
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * he._CHUNK_BYTES * 4
    assert expert_gmm.VMEM_LIMIT <= 100 << 20       # of a v5e core's 128 MiB


# ((B, P, nb) of openpangu-ultra-moe-718b.serve1 and of deepseek-v3.2-exp.serve1,
# T, mq, whether a selection is given)
_DOC, _LONGDOC = (64, 136, 6144), (24, 268, 5120)
_LATENT_CALLS = {"doc-mixed": (_DOC, 512, 64, False), "doc-prefill": (_DOC, 512, 512, False),
                 "doc-decode": (_DOC, 64, 1, False),
                 "longdoc-mixed": (_LONGDOC, 512, 64, True),
                 "longdoc-prefill": (_LONGDOC, 512, 512, True),
                 "longdoc-decode": (_LONGDOC, 24, 1, True)}


@pytest.mark.parametrize("call", _LATENT_CALLS)
def test_latent_attention_attends_in_one_kernel(chip, call, monkeypatch):
    """The latent cache's attention at the two latent cells' serving geometry
    (128 heads over entries of 640, blocks of 64), two iterations in a scan
    with the pool in the carry, compiled as the chip will run it (ISSUE 43):
    ONE ``latent_rows`` call an iteration takes the chunk rows and, where no
    selection is given, the one-token rows (under a selection they gather
    their 2,048 entries, so a decode step holds no kernel), the pool keeps
    the argument's layout and is not copied, and the pass's scores
    ``[tokens, 128 heads, 512 positions]`` in float32 are nowhere in the
    program: they live in the kernel's VMEM."""
    from paddle_tpu.ops import latent_attention as la

    on_the_chip(monkeypatch)
    (B, P, nb), T, mq, selected = _LATENT_CALLS[call]
    H, W, C, bs, K = 128, 640, 512, 64, 2048

    def two_iterations(q, entries, cache, dec, now, cu, bt, *selection):
        def body(cache, _):
            out, cache = la.latent_attention(
                q, entries, cache, dec, now, cu, bt, rank=C, max_q_len=mq, scale=0.07,
                selection=la.Selection(*selection, *(() if mq > 1 else (None,)))
                if selected else None)
            return cache, out
        return jax.lax.scan(body, cache, None, length=2)

    i32, shapes = jnp.int32, []
    if selected:
        shapes = [((B, K), i32), ((B, K), jnp.bool_)] + (
            [((T + mq, P * bs), jnp.bool_)] if mq > 1 else [])
    shapes = [((T, H, W), BF16), ((T, W), BF16), ((nb, bs, W), BF16), ((B,), i32),
              ((B,), i32), ((B + 1,), i32), ((B, P), i32)] + shapes
    if selected and mq == 1:
        compiled = jax.jit(two_iterations, donate_argnums=2).lower(*(
            jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes)).compile()
    else:
        compiled = compile_kernel(two_iterations, chip, *shapes, names=("latent_rows",),
                                  donate=(2,))
    text = compiled.as_text()
    assert kernel_calls(text, "latent_rows") == int(not (selected and mq == 1))
    # the loops' scores: a chunk row's [mq, H, Lc] and the one-token rows'
    # [B, H, Lc] (under a selection [B, H, C] is their accumulator, C = Lc)
    assert f"f32[{mq},128,512]" not in text
    assert selected or f"f32[{B},128,512]" not in text
    pool = f"{nb},{bs},{W}"
    assert set(re.findall(rf"bf16\[{pool}\]\{{([0-9,]+)", text)) == {"2,1,0"}
    assert not re.search(rf"= bf16\[{pool}\][^\n]* copy\(", text)
    # what an iteration holds beside its arguments: the result, the queries'
    # copy and (selected) the gathered entries and the mask's rows: no pass
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (300e6 if selected else 160e6), f"{temp / 1e6:.0f} MB of temporaries"
