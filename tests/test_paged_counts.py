"""Dispatch and count agree: a paged op and the function that tells a trunk
what the op did (``paged_counts``; ``latent_counts`` and ``selection_counts``)
put the SAME question to the kernel's gate, whatever the pool's form.  A trunk
that asked with other sizes than its op would compile one path and count the
other, and ``attn_rows_kernel``, ``attn_chunks_kernel``, ``kv_write_blocks``,
``latent_rows_kernel`` and ``dsa_positions_read`` ride the harvest spans into the benchmark's
per-layer metrics: the gates are wrapped to record what they are asked."""
import functools

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import latent_attention as la
from paddle_tpu.ops import paged_attention as pa

BF16, I32 = jnp.bfloat16, jnp.int32
B, P, BS, T, NB = 3, 4, 16, 24, 12


def sd(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


LENGTHS = (sd((B,), I32), sd((B,), I32), sd((B + 1,), I32), sd((B, P), I32))  # dec, now, cu, bt


@pytest.fixture
def asked(monkeypatch):
    """Wraps the gates of a module: -> {gate: [what each call asked]}."""
    def wrap(module, *gates):
        seen = {g: [] for g in gates}

        def recorder(name, gate):
            @functools.wraps(gate)
            def ask(*args, **kw):
                seen[name].append((tuple(str(jnp.dtype(a)) for a in args), sorted(kw.items())))
                return gate(*args, **kw)
            return ask

        for g in gates:
            monkeypatch.setattr(module, g, recorder(g, getattr(module, g)))
        return seen
    return wrap


# (H, KV, D, the pool a layer holds): heads of 128 a head a row; heads of 64
# two to a lane tile (``lane_packing``); ONE pool with a layer axis
POOLS = {"plain": (4, 2, 128, (NB, 2, BS, 128)),
         "lane_packed": (4, 2, 64, (NB, 1, BS, 128)),
         "stacked": (4, 2, 128, (5, NB, 2, BS, 128)),
         "windowed": (14, 2, 128, (NB, 2, BS, 128))}


@pytest.mark.parametrize("form", POOLS)
def test_blha_attention_and_paged_counts_ask_the_same(form, asked):
    H, KV, D, pool = POOLS[form]
    seen = asked(pa, "decodes_in_kernel", "writes_in_kernel", "chunks_in_kernel")
    layer = {"layer": 2} if form == "stacked" else {}
    window = 24 if form == "windowed" else None
    # the function under the jit: a trace every time, whatever the process cached
    call = functools.partial(
        pa.blha_attention.__wrapped__, num_heads=H, kv_num_heads=KV, head_dim=D,
        block_size=BS, max_q_len=8, compute_dtype=BF16, window=window, **layer)
    jax.eval_shape(lambda qkv, k, v, enc, *lens: call(qkv, k, v, enc, *lens),
                   sd((T, (H + 2 * KV) * D), BF16), sd(pool, BF16), sd(pool, BF16),
                   LENGTHS[0], *LENGTHS)
    dispatched = {g: list(calls) for g, calls in seen.items()}
    assert all(len(calls) == 1 for calls in dispatched.values()), dispatched
    counts = jax.eval_shape(
        lambda k, *lens: pa.paged_counts(BF16, k, *lens, tokens=T, heads=H, max_q_len=8,
                                         window=window), sd(pool, BF16), *LENGTHS)
    assert set(counts) == {"attn_positions_live", "attn_positions_read", "attn_rows_kernel",
                           "attn_chunks_kernel", "kv_write_tokens", "kv_write_blocks"}
    for gate, calls in seen.items():
        assert calls == dispatched[gate] * 2, (gate, calls)
    # and the question is the pool's: rows of 128 lanes in every form
    assert dict(seen["writes_in_kernel"][0][1])["head_dim"] == 128
    assert dict(seen["writes_in_kernel"][0][1])["kv_heads"] == pool[-3]


@pytest.mark.parametrize("window", [None, 100])
def test_a_chunk_rows_read_is_its_tiles_trips_where_the_kernel_takes_it(window, monkeypatch):
    """Blocks of 16 in a table of 64, 7 query heads a key/value head (tiles of
    64 tokens; 32 asked by hand): on the XLA pass a chunk row reads its passes of 512 and its own
    tokens from registers; in ``paged_chunk`` each tile of its tokens the blocks
    from the one that holds ITS first token's first key to its last token's,
    this step's tokens among them.  ``paged_counts`` asks the gate, and counts
    the chunk rows it took."""
    rows = [(600, 1), (520, 40), (0, 0), (30, 3), (1000, 64)]
    dec, now = (jnp.asarray(x, I32) for x in zip(*rows))

    def read(tile):
        return int(pa.attention_positions(dec, now, block_size=16, blocks_per_seq=64, kernel=True,
                                          window=window, chunk_tile=tile, max_q_len=64)[1])

    def blocks(first, last):            # the blocks a tile of tokens first .. last walks
        lo = 0 if window is None else max(first - window + 1, 0) // 16
        return last // 16 + 1 - lo

    one = (blocks(600, 600)) * 16
    # tiles of 32: 520-551 and 552-559; 30-32; 1000-1031 and 1032-1063
    by_tile = (blocks(520, 551) + blocks(552, 559) + blocks(30, 32)
               + blocks(1000, 1031) + blocks(1032, 1063)) * 16
    assert read(32) == one + by_tile
    # one tile of 64 a row walks from the row's first key once
    assert read(64) == one + (blocks(520, 559) + blocks(30, 32) + blocks(1000, 1063)) * 16
    first = (lambda d: 0) if window is None else (lambda d: max(d - window + 1, 0) // 512)
    passes = sum((-(-d // 512) - first(d)) * 512 + n for d, n in rows if n > 1)
    assert read(None) == one + passes
    if window is None:
        assert read(32) == 608 + (35 + 35 + 3 + 65 + 67) * 16
    # the count asks the gate: on the CPU the pass's arithmetic and no chunk row taken ...
    pool, lens = sd((NB, 2, 16, 128), BF16), (dec, now, jnp.zeros((6,), I32), jnp.zeros((5, 64), I32))
    count = lambda: {k: int(v) for k, v in pa.paged_counts(
        BF16, pool, *lens, tokens=128, heads=14, max_q_len=64, window=window).items()}
    assert count()["attn_chunks_kernel"] == 0 == count()["attn_rows_kernel"]
    # ... steered onto the chip the kernels', the three chunk rows counted
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    assert count()["attn_chunks_kernel"] == 3 and count()["attn_rows_kernel"] == 1
    assert count()["attn_positions_read"] == read(64)
    # a call of one token a row has no chunk row to take
    short = {k: int(v) for k, v in pa.paged_counts(
        BF16, pool, dec, jnp.minimum(now, 1), *lens[2:], tokens=128, heads=14, max_q_len=1,
        window=window).items()}
    assert short["attn_chunks_kernel"] == 0 and short["attn_rows_kernel"] == 4


@pytest.mark.parametrize("selected", [False, True])
def test_latent_attention_and_its_counts_ask_the_same(selected, asked):
    H, C, W, mq = 16, 128, 256, 8
    seen = asked(la, "rows_in_kernel")["rows_in_kernel"]
    selection = la.Selection(sd((B, 8), I32), sd((B, 8), jnp.bool_),
                             sd((T + mq, P * BS), jnp.bool_)) if selected else None
    pool = sd((NB, BS, W), BF16)
    jax.eval_shape(
        lambda q, e, cache, sel, *lens: la.latent_attention(
            q, e, cache, *lens, rank=C, max_q_len=mq, scale=0.1, selection=sel),
        sd((T, H, W), BF16), sd((T, W), BF16), pool, selection, *LENGTHS)
    assert len(seen) == 1
    dec, now, _, bt = LENGTHS
    counts = jax.eval_shape(
        lambda cache, now, bt: la.latent_counts(BF16, cache, now, bt, heads=H, rank=C,
                                                selected=selected), pool, now, bt)
    assert set(counts) == {"latent_rows_kernel", "latent_chunks_kernel"}
    if selected:
        counts = jax.eval_shape(
            lambda cache, sel, dec, now, bt: la.selection_counts(
                BF16, cache, dec, now, bt, sel, heads=H, rank=C, topk=8, max_q_len=mq),
            pool, selection, dec, now, bt)
        assert set(counts) == {"dsa_positions_read"}
    assert seen == seen[:1] * (3 if selected else 2), seen
