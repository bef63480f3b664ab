"""The dense cache's chunk rows as a Pallas kernel
(``ops/pallas/paged_chunk.py``), in interpret mode on the CPU, against the XLA
pass of ``ops/paged_attention.py`` as the plain reference: the same call
steered onto the kernel gives the pass's outputs to bf16 rounding and the
pass's pools bit for bit, and an engine steered onto it serves the pass's
tokens."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import programs

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.pallas import paged_chunk as pc
from paddle_tpu.ops.pallas.latent_rows import work_items
from paddle_tpu.ops.pallas import paged_decode as pd
from paddle_tpu.ops.pallas import paged_write as pw

BF16 = jnp.bfloat16
B, P, BS, T, NB = 6, 12, 64, 160, 60
WINDOW = 200                        # a little over three blocks


def _call(dec, now, *, H, KV, D=128, window=None, behind=True, holes=(), layers=None,
          bs=BS, seed=0):
    """``blha_attention``'s eight arrays: every live row holds the blocks of its
    context (``behind`` False: those behind its first token's window given
    back), ``holes`` [(row, column)] taken out again."""
    rng = np.random.default_rng(seed)
    dec, now = np.asarray(dec, np.int32), np.asarray(now, np.int32)
    rows = len(dec)
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    assert cu[-1] <= T
    bt = np.full((rows, P * BS // bs), -1, np.int32)
    free = list(rng.permutation(NB * BS // bs))
    for b in range(rows):
        lo = 0 if behind or window is None else max(dec[b] - (window - 1), 0) // bs
        for j in range(lo, -(-(dec[b] + now[b]) // bs) if now[b] else 0):
            bt[b, j] = free.pop()
    for hole in holes:
        bt[hole] = -1
    pool = (NB * BS // bs,) + pa.lane_packing(KV, D)[1](bs)
    if layers:
        pool = (layers,) + pool
    return (jnp.asarray(rng.normal(size=(T, (H + 2 * KV) * D)) * 0.5, BF16),
            jnp.asarray(rng.normal(size=pool), BF16), jnp.asarray(rng.normal(size=pool), BF16),
            jnp.zeros((rows,), jnp.int32), jnp.asarray(dec), jnp.asarray(now),
            jnp.asarray(cu), jnp.asarray(bt))


def _steer(monkeypatch, calls=None, *, alone=True, ctx_block=128):
    """``on_tpu`` answers yes in ``ops/paged_attention.py`` and ``paged_chunk``
    runs in interpret mode, in passes of ``ctx_block`` positions (two blocks:
    the contexts here make the trips the cells' make at 512); ``calls`` grows
    by one with every ``paged_chunk`` call traced.  ``alone``: the one-token
    rows and the write keep the XLA pass and the scatter (their kernels have
    their own files, and an interpreted trace of each costs seconds); else
    they run interpreted too, as the chip composes the three."""
    def chunk(*a, **k):
        if calls is not None:
            calls.append(k)
        return pc.paged_chunk(*a, interpret=True, ctx_block=ctx_block, **k)

    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    monkeypatch.setattr(pa, "paged_chunk", chunk)
    if alone:
        monkeypatch.setattr(pa, "decodes_in_kernel", lambda *a, **k: False)
        monkeypatch.setattr(pa, "writes_in_kernel", lambda *a, **k: False)
    else:
        monkeypatch.setattr(pa, "paged_decode",
                            functools.partial(pd.paged_decode, interpret=True))
        monkeypatch.setattr(pa, "paged_write", functools.partial(pw.paged_write, interpret=True))


_TRACED = {}    # (steered, the call's statics) -> (the jitted call, the paged_chunk calls it traced)


def _attend(args, monkeypatch=None, layer=None, **kw):
    """The function under the jit, traced once a (steering, statics) of this file:
    the platform is asked as the FIRST test to come has steered it (``monkeypatch``
    given: onto the kernel), and cases that differ in data share the trace.
    Returns (the call's outputs, the ``paged_chunk`` calls its trace made)."""
    key = (monkeypatch is not None, tuple(sorted(kw.items())))
    if key not in _TRACED:
        _TRACED[key] = (jax.jit(functools.partial(pa.blha_attention.__wrapped__, **kw)), [])
    fn, calls = _TRACED[key]
    if monkeypatch is not None:
        _steer(monkeypatch, calls)
    return fn(*args, **({} if layer is None else {"layer": layer})), calls


def _close(got, want, heads):
    """To bf16 rounding: a token's head within two steps of ITS largest value
    (the float32 sums differ by their order, the pass's probabilities are not
    rounded on the CPU)."""
    got, want = (np.asarray(a, np.float32).reshape(T, heads, -1) for a in (got, want))
    assert np.isfinite(got).all()
    scale = np.abs(want).max(axis=-1, keepdims=True)
    assert (np.abs(got - want) <= 2.0 ** -6 * scale + 1e-6).all(), np.abs(got - want).max()


# rows at rest and one-token rows between chunk rows; chunks that end inside a
# tile (40 = 32 + 8 as pieces, 33, 17), start at 0, and cross blocks and a pass
ROWS = ([0, 520, 130, 5, 600, 250], [40, 0, 33, 1, 1, 17])
CASES = {
    # name: (H, KV, D, max_q_len, (dec, now), window, blocks behind it present, holes)
    **{f"g{g}-{w}": (KV * g, KV, 128, 64, ROWS, WINDOW if w != "whole" else None,
                     w != "given-back", ())
       for g, KV in ((1, 4), (4, 2), (7, 2)) for w in ("whole", "window", "given-back")
       if g > 1 or w == "whole"},
    "heads of 64 two to a lane tile": (8, 4, 64, 64, ROWS, None, True, ()),
    "heads of 64 under a window given back": (8, 4, 64, 64, ROWS, WINDOW, False, ()),
    "a hole in the table": (8, 2, 128, 64, ROWS, None, True, ((1, 3), (4, 0), (5, 1))),
    "drafts of three": (8, 2, 128, 3, ([30, 520, 130, 63, 600, 0], [3, 3, 2, 3, 1, 3]),
                        None, True, ()),
    "a prefill of several tiles": (8, 2, 128, T, ([0, 0, 64, 0, 0, 300], [70, 0, 1, 0, 0, 85]),
                                   None, True, ()),
    "a prefill under a window": (14, 2, 128, T, ([0, 0, 64, 0, 0, 300], [70, 0, 1, 0, 0, 85]),
                                 WINDOW, False, ()),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_gives_the_passes_outputs_and_pools(name, monkeypatch):
    H, KV, D, mq, (dec, now), window, behind, holes = CASES[name]
    args = _call(dec, now, H=H, KV=KV, D=D, window=window, behind=behind, holes=holes)
    kw = dict(num_heads=H, kv_num_heads=KV, head_dim=D, block_size=BS, max_q_len=mq,
              compute_dtype=BF16, window=window)
    want, _ = _attend(args, **kw)
    got, calls = _attend(args, monkeypatch, **kw)
    assert len(calls) == 1 and calls[0]["max_q_len"] == mq and calls[0]["window"] == window
    _close(got[0], want[0], H)
    for mine, theirs in zip(got[1:3], want[1:3]):
        assert mine.dtype == BF16 and np.array_equal(
            np.asarray(mine).view(np.uint16), np.asarray(theirs).view(np.uint16))
    live = np.arange(T) < np.sum(now)
    out = np.asarray(got[0], np.float32)
    assert not out[~live].any() and np.abs(out[live]).max(axis=1).min() > 0


@pytest.mark.parametrize("layer", [0, 2])
def test_a_stacked_pools_layer_is_its_own_blocks(layer, monkeypatch):
    """A looped model's ONE pool: the table's entries moved to the layer's
    blocks before the call, the other layers untouched and unread."""
    dec, now = ROWS
    args = _call(dec, now, H=4, KV=4, layers=3)
    kw = dict(num_heads=4, kv_num_heads=4, head_dim=128, block_size=BS, max_q_len=64,
              compute_dtype=BF16)
    want, _ = _attend(args, layer=jnp.int32(layer), **kw)
    got, calls = _attend(args, monkeypatch, layer=jnp.int32(layer), **kw)
    assert len(calls) == 1
    _close(got[0], want[0], 4)
    others = [l for l in range(3) if l != layer]
    for mine, theirs, before in zip(got[1:3], want[1:3], args[1:3]):
        assert np.array_equal(np.asarray(mine).view(np.uint16), np.asarray(theirs).view(np.uint16))
        assert np.array_equal(np.asarray(mine)[others].view(np.uint16),
                              np.asarray(before)[others].view(np.uint16))
    # another layer's keys would have given another answer
    other, _ = _attend(args, monkeypatch, layer=jnp.int32(1), **kw)
    assert np.abs(np.asarray(other[0], np.float32) - np.asarray(got[0], np.float32)).max() > 0.01


@pytest.mark.parametrize("g,window", [(4, None), (7, WINDOW)])
def test_the_kernel_alone_writes_its_rows_live_tokens_and_no_other(g, window):
    """``paged_chunk`` itself: the result comes in and goes out; the tokens of
    rows that feed none or one, the tokens past ``cu[-1]`` and a short tile's
    tail keep what the caller put there, the heads that are padding stay
    zeros, and the pools are not written."""
    KV, D = 2, 128
    H = KV * g
    dec, now = ROWS
    _, kc, vc, _, dec, now, cu, bt = _call(dec, now, H=H, KV=KV, window=window, behind=False)
    q = jnp.asarray(np.random.default_rng(3).normal(size=(T, H, D)), BF16)
    Hp = pc.padded_heads(H)
    assert Hp % 16 == 0 and 0 <= Hp - H < 16
    before = [np.asarray(pool).view(np.uint16).copy() for pool in (kc, vc)]
    marks = jnp.broadcast_to(jnp.arange(T + 64, dtype=jnp.float32)[:, None, None] + 1000.0,
                             (T + 64, Hp, D))
    out = pc.paged_chunk(q, kc, vc, marks, dec, now, cu, bt, scale=D ** -0.5, max_q_len=64,
                         window=window, ctx_block=128, interpret=True)
    out = np.asarray(out)
    written = np.zeros(T + 64, bool)
    for b in range(B):
        if now[b] > 1:
            written[int(cu[b]):int(cu[b] + now[b])] = True
    assert np.array_equal(out[~written], np.asarray(marks)[~written])
    assert np.abs(out[written][:, :H]).max() < 100 and not out[written][:, H:].any()
    for pool, was in zip((kc, vc), before):
        assert np.array_equal(np.asarray(pool).view(np.uint16), was)


def test_the_items_are_the_chunk_rows_tiles_in_the_rows_order():
    dec = jnp.asarray([0, 520, 130, 5, 600, 250], jnp.int32)
    now = jnp.asarray([40, 0, 33, 1, 1, 17], jnp.int32)
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(now)])
    count, items = work_items(dec, now, cu, now > 1, tokens=T, tile=32)
    n = int(count[0])
    rows, starts, bases, tokens = np.asarray(items).reshape(4, -1)[:, :n]
    assert n == 5 and items.shape == (4 * (T // 32 + B),)
    assert rows.tolist() == [0, 0, 2, 2, 5]
    assert starts.tolist() == [0, 32, 40, 72, 75]
    assert bases.tolist() == [0, 32, 130, 162, 250]
    assert tokens.tolist() == [32, 8, 32, 1, 17]
    # tokens past cu[-1] are no row's: the buffer's end cuts a row short
    count, items = work_items(dec, now, cu.at[-1].set(50), now > 1, tokens=T, tile=32)
    assert int(count[0]) == 3
    assert np.asarray(items).reshape(4, -1)[3, :3].tolist() == [32, 8, 10]


@pytest.mark.parametrize("group,mq,tile", [(1, 64, 64), (4, 64, 64), (7, 64, 64), (8, 64, 64),
                                           (16, 64, 32), (32, 64, 16), (4, 3, 16), (4, 20, 32),
                                           (1, 512, 64), (7, 512, 64)])
def test_the_tile_follows_the_calls_shapes(group, mq, tile):
    """64 tokens while ``g x tokens`` stays within 512 query rows a KV head, a
    power of two of whole bf16 tiles, no more than the longest row rounded up
    to one."""
    assert pc.tile_tokens(group, mq) == tile


def test_the_cpu_a_float32_pool_and_a_mask_keep_the_pass(monkeypatch):
    dec, now = ROWS
    kw = dict(num_heads=8, kv_num_heads=2, head_dim=128, block_size=BS, max_q_len=64)
    args = _call(dec, now, H=8, KV=2)
    call = pa.blha_attention.__wrapped__
    monkeypatch.setattr(pa, "paged_chunk", None)            # never reached
    jax.eval_shape(functools.partial(call, compute_dtype=BF16, **kw), *args)     # the CPU
    _steer(monkeypatch)
    monkeypatch.setattr(pa, "paged_chunk", None)
    as_f32 = [a.astype(jnp.float32) if a.dtype == BF16 else a for a in args]
    jax.eval_shape(functools.partial(call, compute_dtype=jnp.float32, **kw), *as_f32)
    jax.eval_shape(functools.partial(call, compute_dtype=BF16, **kw), *args,
                   mask=jnp.zeros((B, 1, 64, P * BS + 64), jnp.float32))
    with pytest.raises(TypeError):                          # and a call it admits reaches it
        jax.eval_shape(functools.partial(call, compute_dtype=BF16, **kw), *args)
    sizes = dict(head_dim=128, block_size=BS, rows=B, blocks_per_seq=P, tokens=T, kv_heads=2)
    assert pa.chunks_in_kernel(BF16, BF16, **sizes)
    assert not pa.chunks_in_kernel(BF16, BF16, **dict(sizes, plain=False))
    assert not pa.chunks_in_kernel(BF16, BF16, **dict(sizes, head_dim=64))
    assert not pa.chunks_in_kernel(BF16, BF16, **dict(sizes, block_size=8))
    assert not pa.chunks_in_kernel(BF16, jnp.uint8, **sizes)
    assert not pa.chunks_in_kernel(jnp.float32, BF16, **sizes)
    assert not pa.chunks_in_kernel(BF16, BF16, **dict(sizes, rows=4096, blocks_per_seq=64))
    assert not pa.chunks_in_kernel(BF16, BF16, **dict(sizes, kv_heads=128))


def test_the_three_kernels_compose_at_the_cells_pass(monkeypatch):
    """As the chip traces the call: the write's kernel, the one-token rows' and
    the chunk rows', each interpreted, the chunk rows in passes of 512."""
    H, KV = 8, 2
    dec, now = ROWS
    args = _call(dec, now, H=H, KV=KV)
    kw = dict(num_heads=H, kv_num_heads=KV, head_dim=128, block_size=BS, max_q_len=64,
              compute_dtype=BF16)
    want, _ = _attend(args, **kw)
    calls = []
    _steer(monkeypatch, calls, alone=False, ctx_block=512)
    got = jax.jit(functools.partial(pa.blha_attention.__wrapped__, **kw))(*args)
    assert len(calls) == 1
    _close(got[0], want[0], H)
    for mine, theirs in zip(got[1:3], want[1:3]):
        assert np.array_equal(np.asarray(mine).view(np.uint16), np.asarray(theirs).view(np.uint16))


# ------------------------------------------------ engines through the kernel
def _served(model, prompts, geometry, new=8):
    from paddle_tpu.inference import ServingEngine

    eng = ServingEngine(model, **geometry)
    seen = programs.harvests(eng)
    rids = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    out = eng.run()
    return eng, seen, [out[r] for r in rids]


def _both(model, prompts, geometry, monkeypatch):
    """(the unsteered engine, the steered one, what its launches harvested);
    the tokens served are the same."""
    from paddle_tpu.inference import serving

    plain, _, want = _served(model, prompts, geometry)
    assert plain.attn_chunks_kernel == 0
    # the platform is asked when a program is traced: drop the traces made for
    # the CPU, and those made here once the test is over
    monkeypatch.setattr(serving, "_PROGRAM_CACHE", {})
    _steer(monkeypatch, ctx_block=512)
    pa.blha_attention.clear_cache()
    try:
        eng, seen, got = _served(model, prompts, geometry)
    finally:
        pa.blha_attention.clear_cache()
    assert got == want
    return plain, eng, seen


def _llama():
    import paddle_tpu as P_
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    set_hybrid_communicate_group(None)
    P_.seed(5)
    net = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=256, intermediate_size=256, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, max_position_embeddings=128,
        dtype="bfloat16"))
    net.bfloat16()
    return net.eval()


def test_a_llama_engine_steered_onto_the_chip_feeds_its_chunks_to_the_kernel(monkeypatch):
    """``step`` (both prompts' first tokens), ``mixed`` (the long prompt's later
    chunks beside the short one's decoding) and ``spec_verify`` (drafts of two:
    every row a chunk row of 3): the chunk rows of every launch attend in
    ``paged_chunk``, counted by the trunk, added up by the engine, on the
    harvest span and in the summary; the tokens are the XLA pass's."""
    geometry = dict(max_batch_size=2, max_seq_len=96, block_size=16, token_budget=16,
                    megastep_k=4, spec_k=2)
    plain, eng, seen = _both(_llama(), programs.prompts([5, 44], seed=2, vocab=128), geometry,
                             monkeypatch)
    kinds = {k for k, _, _ in seen}
    assert {"step", "mixed", "spec"} <= kinds
    for kind in kinds - {"mega"}:
        assert sum(a["attn_chunks_kernel"] for k, _, a in seen if k == kind) > 0, kind
    assert all(a["attn_chunks_kernel"] == 0 for k, _, a in seen if k == "mega")
    assert eng.attn_chunks_kernel == sum(a["attn_chunks_kernel"] for _, _, a in seen) > 0
    assert eng.state_summary()["attention"]["chunks_kernel"] == eng.attn_chunks_kernel
    assert plain.state_summary()["attention"]["chunks_kernel"] == 0
    assert eng.attn_positions_live == plain.attn_positions_live
    # the mixed launches' share: a prompt chunk an iteration fed is a chunk row
    # unless it is the prompt's one-token tail (the other kernel's)
    mixed = sum(a["attn_chunks_kernel"] for k, _, a in seen if k == "mixed")
    assert 0 < mixed <= plain.prefill_chunks


def test_a_two_kind_engine_counts_its_chunks_by_kind(monkeypatch):
    """SmallThinker: a global and a window kind of cache layer, each its own
    pool and table, the window kind's blocks given back behind the window: the
    kernel walks from the window's first block, and the tokens are the XLA
    pass's."""
    cfg = dict(programs.TINY["smallthinker"], torch_dtype="bfloat16", head_dim=128,
               hidden_size=128, num_attention_heads=2, num_key_value_heads=1,
               num_hidden_layers=2, sliding_window_layout=[0, 1], rope_layout=[0, 1],
               sliding_window_size=32)
    model = programs.build("smallthinker", cfg)[0]
    geometry = dict(max_batch_size=2, max_seq_len=96, block_size=16, token_budget=16,
                    megastep_k=4, num_blocks={"global": 14, "window": 10})
    plain, eng, seen = _both(model, programs.prompts([70, 9], seed=4), geometry, monkeypatch)
    assert eng.attn_chunks_kernel > 0 and eng.window_blocks_released > 0
    by_kind = eng.state_summary()["attention_by_kind"]
    assert by_kind["attn_chunks_kernel.global"] == eng.attn_chunks_kernel
    assert by_kind["attn_chunks_kernel.window"] == eng.attn_chunks_kernel
    assert by_kind["attn_positions_read.window"] < by_kind["attn_positions_read.global"]
    assert "attn_chunks_kernel.window" not in plain.state_summary()["attention_by_kind"] or \
        plain.state_summary()["attention_by_kind"]["attn_chunks_kernel.window"] == 0
