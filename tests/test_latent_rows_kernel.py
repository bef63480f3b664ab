"""The latent cache's blocked pass as a Pallas kernel
(``ops/pallas/latent_rows.py``), in interpret mode on the CPU, against the XLA
loops of ``ops/latent_attention.py`` as the plain reference: the same call
steered onto the kernel gives the loops' outputs to bf16 rounding and the
loops' cache bit for bit."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import latent_attention as la
from paddle_tpu.ops.latent_attention import Selection, latent_attention
from paddle_tpu.ops.pallas.latent_rows import latent_rows

B, P, BS, H, W, C, T = 8, 17, 64, 16, 256, 128, 64
NB = 140
L = 512                                     # positions a pass brings (8 blocks)
CONTEXTS = (1, BS - 1, BS, BS + 1, L - 1, L, L + 1, 2 * L + 1)


def _call(dec, now, *, seed=0, holes=()):
    """(q, entries, cache, dec, now, cu, block tables): every live row holds
    the blocks of its context, ``holes`` [(row, column)] taken out again."""
    rng = np.random.default_rng(seed)
    dec, now = np.asarray(dec, np.int32), np.asarray(now, np.int32)
    cu = np.concatenate([[0], np.cumsum(now)]).astype(np.int32)
    assert cu[-1] <= T
    bt = np.full((B, P), -1, np.int32)
    free = list(rng.permutation(NB))
    for b in range(B):
        for j in range(-(-(dec[b] + now[b]) // BS) if now[b] else 0):
            bt[b, j] = free.pop()
    for hole in holes:
        bt[hole] = -1
    return (jnp.asarray(rng.normal(size=(T, H, W)) * 0.5, jnp.bfloat16),
            jnp.asarray(rng.normal(size=(T, W)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(NB, BS, W)), jnp.bfloat16),
            jnp.asarray(dec), jnp.asarray(now), jnp.asarray(cu), jnp.asarray(bt))


def _selection(dec, now, mq, hidden):
    """Every position selected but the columns ``hidden``; ``"causal"``:
    nothing past a token's own position."""
    now = np.asarray(now)
    width = P * BS
    idx = jnp.tile(jnp.arange(32, dtype=jnp.int32), (B, 1))
    mask = np.ones((T + mq, width), bool)
    if hidden == "causal":
        cu = np.concatenate([[0], np.cumsum(now)])
        for b in range(B):
            for t in range(now[b]):
                mask[cu[b] + t] = np.arange(width) <= dec[b] + t
    else:
        mask[:, list(hidden)] = False
    ok = (idx <= jnp.asarray(dec)[:, None]) & ~jnp.isin(idx, jnp.asarray(list(
        () if hidden == "causal" else hidden), jnp.int32))
    return Selection(idx, ok, jnp.asarray(mask))


ONES = ([c - 1 for c in CONTEXTS], [1] * 8)
# contexts of 63 ... 1025 as chunks of 2-16 tokens, one token at a context of 1
CHUNKS = ([61, 60, 49, 500, 496, 505, 1020, 0], [2, 4, 16, 11, 16, 8, 5, 1])
# rows at rest between live rows, both kinds of row, chunks shorter than mq
MIXED = ([700, 130, 0, 64, 300, 9, 512, 40], [0, 1, 13, 0, 16, 1, 3, 0])
# contexts short enough for one-token rows to list their positions (32 of them)
SELECTED = ([20, 20, 0, 31, 300, 9, 512, 40], [0, 1, 13, 1, 16, 1, 3, 0])

CASES = {
    # name: (max_q_len, (dec, now), holes in the table, selection or its hidden columns)
    "one-token rows at every edge of a block and a pass": (1, ONES, (), None),
    "chunk rows at every edge of a block and a pass": (16, CHUNKS, (), None),
    "both kinds of row and rows at rest": (16, MIXED, (), None),
    "a short chunk bound": (4, ([700, 130, 0, 64, 300, 9, 512, 40],
                                [0, 1, 3, 0, 4, 1, 2, 0]), (), None),
    "a hole in the table": (16, MIXED, ((1, 1), (4, 0), (6, 7)), None),
    "a selection of everything": (16, SELECTED, (), ()),
    "a selection of nothing past the causal edge": (16, SELECTED, (), "causal"),
    "hidden positions": (16, SELECTED, (), (2, 65, 300)),
    "hidden positions and a hole": (16, SELECTED, ((4, 1),), (2, 65, 300)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_gives_the_loops_outputs_and_cache(name, monkeypatch):
    mq, (dec, now), holes, hidden = CASES[name]
    args = _call(dec, now, holes=holes)
    selection = None if hidden is None else _selection(dec, now, mq, hidden)
    kw = dict(rank=C, max_q_len=mq, scale=0.11, selection=selection)
    want, cache = latent_attention(*args, **kw)
    plain, _ = latent_attention(*args, **dict(kw, selection=None))
    calls = []

    def kernel(*a, **k):
        calls.append(a[7])                      # the rows the kernel is to take
        return latent_rows(*a, interpret=True, **k)

    monkeypatch.setattr(la, "on_tpu", lambda: True)
    monkeypatch.setattr(la, "latent_rows", kernel)
    got, cache_k = latent_attention(*args, **kw)

    now = np.asarray(now)
    taken = (now > 1) if selection is not None else (now > 0)
    assert len(calls) == 1 and np.array_equal(np.asarray(calls[0]), taken)
    want, got, plain = (np.asarray(a, np.float32) for a in (want, got, plain))
    assert np.isfinite(got).all()
    # to bf16 rounding: the float32 results differ by the order of their sums
    assert (np.abs(got - want) <= 2.0 ** -7 * np.maximum(np.abs(want), np.abs(got)) + 1e-6).all()
    assert np.array_equal(np.asarray(cache_k), np.asarray(cache))
    live = np.arange(T) < now.sum()
    assert not got[~live].any() and np.abs(got[live]).min(axis=(1, 2)).min() > 0
    if hidden in ((), "causal"):
        assert np.abs(got - plain).max() <= 2.0 ** -6
    elif hidden:
        # a token moved if and only if it could see a hidden position
        position = np.concatenate([d + np.arange(n) for d, n in zip(dec, now)])
        moved = np.abs(got - plain)[live].max(axis=(1, 2)) > 1e-4
        assert np.array_equal(moved, position >= min(hidden))


def test_the_cpu_and_a_float32_pool_keep_the_loops(monkeypatch):
    args = _call(*MIXED)
    monkeypatch.setattr(la, "latent_rows", None)            # never reached
    latent_attention(*args, rank=C, max_q_len=16, scale=0.11)
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    as_f32 = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a for a in args]
    latent_attention(*as_f32, rank=C, max_q_len=16, scale=0.11)
    assert not la.rows_in_kernel(jnp.bfloat16, jnp.bfloat16, heads=H, width=576, rank=512,
                                 block_size=BS, rows=B, blocks_per_seq=P)
    assert la.rows_in_kernel(jnp.bfloat16, jnp.bfloat16, heads=H, width=W, rank=C,
                             block_size=BS, rows=B, blocks_per_seq=P)
    ones, chunks = la.rows_taken(args[4], kernel=True, selected=False)
    assert (int(ones), int(chunks)) == (2, 3)
    ones, chunks = la.rows_taken(args[4], kernel=True, selected=True)
    assert (int(ones), int(chunks)) == (0, 3)
    assert [int(n) for n in la.rows_taken(args[4], kernel=False, selected=False)] == [0, 0]


# sha256 (first 16 hex digits) of ``latent_attention``'s lowered text ALONE at
# this file's geometry, jax 0.9.0, taken at the PARENT commit (62acd16): a
# call the kernel does not admit (the CPU, whatever the pool; a float32 pool,
# whatever the platform) is the parent's program to the letter.
PARENT_TEXTS = {
    ("float32", 1, False): "df7a77e4537d960a", ("float32", 1, True): "db537699568fcbae",
    ("float32", 16, False): "6b3c632342496dd1", ("float32", 16, True): "a218e83d441f3003",
    ("bfloat16", 1, False): "566b7033575ed92c", ("bfloat16", 1, True): "61a5d46457ad2c6b",
    ("bfloat16", 16, False): "a83aa8187ae210f6", ("bfloat16", 16, True): "1348a2f501243da7"}


@pytest.mark.skipif(jax.__version__ != "0.9.0", reason="the texts are jax 0.9.0's")
@pytest.mark.parametrize("dtype,mq,selected", sorted(PARENT_TEXTS))
def test_a_call_the_kernel_does_not_admit_lowers_to_the_parents_text(
        dtype, mq, selected, monkeypatch):
    if dtype == "float32":
        monkeypatch.setattr(la, "on_tpu", lambda: True)     # the pool alone keeps it off
    sd = jax.ShapeDtypeStruct
    args = (sd((T, H, W), dtype), sd((T, W), dtype), sd((NB, BS, W), dtype),
            sd((B,), jnp.int32), sd((B,), jnp.int32), sd((B + 1,), jnp.int32),
            sd((B, P), jnp.int32))
    selection = Selection(sd((B, 32), jnp.int32), sd((B, 32), jnp.bool_),
                          sd((T + mq, P * BS), jnp.bool_)) if selected else None
    text = jax.jit(lambda *a: latent_attention(
        *a[:7], rank=C, max_q_len=mq, scale=0.11, selection=a[7])).lower(
            *args, selection).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_TEXTS[dtype, mq, selected]


# ------------------------------------------------ an engine through the kernel
def _tiny(family):
    """A bf16 latent model the kernel admits: 16 heads over a latent of 128
    (entries stored 256 wide), blocks of 16."""
    import programs

    cfg = dict(programs.TINY[family], torch_dtype="bfloat16", kv_lora_rank=128,
               num_attention_heads=16, num_key_value_heads=16)
    return programs.build(family, cfg)[0]


@pytest.mark.parametrize("family", ["pangu", "deepseek"])
def test_an_engine_steered_onto_the_chip_attends_in_the_kernel(family, monkeypatch):
    """With ``on_tpu`` answering yes in ``ops/latent_attention.py`` (the
    kernel in interpret mode) a latent engine serves the tokens the loops
    serve, and counts the rows the kernel took: openPangu's every row, of
    DeepSeek-V3.2's (a selection) the chunk rows alone."""
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.inference import ServingEngine, serving

    set_hybrid_communicate_group(None)
    model = _tiny(family)
    geometry = dict(max_batch_size=4, max_seq_len=96, block_size=16, token_budget=32,
                    megastep_k=4)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, n).tolist() for n in (21, 40, 9)]

    def run():
        eng = ServingEngine(model, **geometry)
        rids = [eng.add_request(p, max_new_tokens=10, sampling={"logprobs": True})
                for p in prompts]
        out = eng.run()
        lps = eng.pop_token_logprobs()
        return eng, [out[r] for r in rids], [np.asarray(lps[r]) for r in rids]

    plain, want, want_lps = run()
    assert plain.latent_rows_kernel == 0 == plain.latent_chunks_kernel
    # the platform is asked when a program is traced: drop the CPU's traces
    monkeypatch.setattr(serving, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    monkeypatch.setattr(la, "latent_rows", lambda *a, **k: latent_rows(*a, interpret=True, **k))
    eng, got, got_lps = run()
    assert got == want
    assert max(np.abs(a - b).max() for a, b in zip(got_lps, want_lps)) < 0.05
    assert eng.latent_chunks_kernel > 0
    # the rows that fed one token: every token a request emitted but the one
    # its prompt's last chunk gave (a one-token tail of a prompt is such a row too)
    ones = eng.latent_rows_kernel
    assert (ones >= 3 * 9) if family == "pangu" else ones == 0
    assert eng.state_summary()["latent_attention"] == {
        "rows_kernel": ones, "chunks_kernel": eng.latent_chunks_kernel}
    if family == "deepseek":
        assert eng.dsa_positions_read <= plain.dsa_positions_read
        assert eng.dsa_positions_selected == plain.dsa_positions_selected


def test_what_the_kernel_brings_is_counted_by_its_own_arithmetic():
    """``selection_reads(kernel=True)``: a chunk row's query at a position >=
    ``topk`` brings the blocks up to the last token of its TILE of 16 tokens
    (no pass of 512 rounds it up, and a tile does not wait for the row's
    end); a one-token row its gathered entries, as before."""
    from paddle_tpu.ops.pallas.latent_rows import TILE_TOKENS

    assert TILE_TOKENS == 16
    dec = jnp.asarray([13, 4, 40, 3, 70, 0], jnp.int32)
    now = jnp.asarray([1, 11, 8, 1, 0, 30], jnp.int32)
    kw = dict(topk=8, gathered=8, block_size=8, blocks_per_seq=12, ctx_block=16)
    got = la.selection_reads(dec, now, kernel=True, max_q_len=30, **kw)
    # row 0: 8; row 1: positions 8..14, 7 queries, the tile ends at 15 -> 16;
    # row 2: 8 queries x 48; row 3: a context of 4; row 4 at rest; row 5: the
    # tiles end at 16 and 30 -> 8 x 16 + 14 x 32
    assert int(got) == 8 + 7 * 16 + 8 * 48 + 0 + 0 + (128 + 448)
    assert int(got) <= int(la.selection_reads(dec, now, **kw))
