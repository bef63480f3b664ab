"""Dispatch and count agree: a paged op and the function that tells a trunk
what the op did (``paged_counts``; ``latent_counts`` and ``selection_counts``)
put the SAME question to the kernel's gate, whatever the pool's form.  A trunk
that asked with other sizes than its op would compile one path and count the
other, and ``attn_rows_kernel``, ``kv_write_blocks``, ``latent_rows_kernel``
and ``dsa_positions_read`` ride the harvest spans into the benchmark's
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
         "stacked": (4, 2, 128, (5, NB, 2, BS, 128))}


@pytest.mark.parametrize("form", POOLS)
def test_blha_attention_and_paged_counts_ask_the_same(form, asked):
    H, KV, D, pool = POOLS[form]
    seen = asked(pa, "decodes_in_kernel", "writes_in_kernel")
    layer = {"layer": 2} if form == "stacked" else {}
    # the function under the jit: a trace every time, whatever the process cached
    call = functools.partial(
        pa.blha_attention.__wrapped__, num_heads=H, kv_num_heads=KV, head_dim=D,
        block_size=BS, max_q_len=8, compute_dtype=BF16, **layer)
    jax.eval_shape(lambda qkv, k, v, enc, *lens: call(qkv, k, v, enc, *lens),
                   sd((T, (H + 2 * KV) * D), BF16), sd(pool, BF16), sd(pool, BF16),
                   LENGTHS[0], *LENGTHS)
    dispatched = {g: list(calls) for g, calls in seen.items()}
    assert all(len(calls) == 1 for calls in dispatched.values()), dispatched
    counts = jax.eval_shape(
        lambda k, *lens: pa.paged_counts(BF16, k, *lens, tokens=T), sd(pool, BF16), *LENGTHS)
    assert set(counts) == {"attn_positions_live", "attn_positions_read", "attn_rows_kernel",
                           "kv_write_tokens", "kv_write_blocks"}
    for gate, calls in seen.items():
        assert calls == dispatched[gate] * 2, (gate, calls)
    # and the question is the pool's: rows of 128 lanes in every form
    assert dict(seen["writes_in_kernel"][0][1])["head_dim"] == 128
    assert dict(seen["writes_in_kernel"][0][1])["kv_heads"] == pool[-3]


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
