"""The plain reference of the ``looped_dense`` family (Ouro, ``model_type``
``ouro``: "Scaling Latent Reasoning via Looped Language Models"): ONE stack of
sandwich-normed dense layers run ``total_ut_steps`` times over the same
weights.  Straightforward jax.numpy in float32 under ``highest`` matmul
precision: no cache, no kernel, no batching, nothing imported from the
program.  Every pass is a full causal forward over the whole sequence (prompt
and served tokens), attending only the keys and values it computed itself.

    x_0 = Emb(ids); for r = 0 .. R-1:   x_{r+1} = norm(L_{depth-1}(... L_0(x_r)))
    layer l:  a = x + n2_l(Attn_l(n1_l(x)));  y = a + n4_l(MLP_l(n3_l(a)))
    Attn_l:   q, k, v = h Wq, h Wk, h Wv; rotate-half RoPE on q and k at the
              token's position, the same in every pass; causal softmax,
              scale head_dim^-0.5; then Wo.  No bias anywhere.
    MLP_l:    (silu(h Wg) * (h Wu)) Wd
    gate:     lambda_r = sigmoid(w_g . x_{r+1} + b_g);
              p(r) = lambda_r prod_{j<r}(1 - lambda_j), the last pass taking
              what is left; a token leaves at the first pass whose cumulative
              p reaches ``early_exit_threshold``.  At the published 1 that is
              the last pass, and the logits are x_R W_head.

What the published config.json does not say and this file assumes (the
configuration file lists the same under ``assumed``): the sandwich (two
further RMSNorms a layer, on the attention's and the MLP's outputs before
the residual adds), the model's final norm inside the loop after every pass,
the gate's form, one set of keys and values a (pass, layer), no bias,
rotate-half RoPE with no scaling.

It takes the benchmark's weights (benchmark/families/looped_dense.make_weights:
bf16 arrays, matrices [in, out], the layers' leaves STACKED on a leading depth
axis, ``wqkv`` the three attention projections side by side) and up-casts
one layer at a time, so that it fits beside them.

``quant="int8"`` is the control: every matmul by a weight in W8A8, as
benchmark/references/llama_dense.py does it.  ``quant="bf16"`` is the
witness of what bf16 arithmetic ALONE does to these equations: the same
plain code with the residual stream, every matmul's inputs and every
layer's intermediate results kept in bf16 (sums in float32, as the chip's
matmul unit and any bf16 model do), still no cache and no kernel.  Run as a
script (``python3 -m benchmark.references.looped_dense --config
ouro-2.6b.serve1 --seed N``) it reads both against its float32 self, by the
statistic the benchmark's check computes: where a served model's gap lies
with the bf16 witness's, the gap is the precision's and no fault's."""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16


def _rms(x, w, eps):
    h = x.astype(F32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps) * w.astype(F32)
    return h.astype(x.dtype)


def _rope(x, theta):
    """x: [S, H, D]; positions 0..S-1; halves rotated (the HF convention)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2].astype(F32), x[..., d // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _q8(x, axis):
    """Round to 8-bit integers, symmetric, one scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, quant):
    """x @ w, summed in float32 and returned so, whatever went in."""
    if quant == "bf16":
        return jnp.matmul(x.astype(BF16), w.astype(BF16), preferred_element_type=F32)
    x, w = x.astype(F32), w.astype(F32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown lower precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def layer_forward(stack, l, x, *, heads, kv_heads, head_dim, eps, theta, quant=None):
    """Layer ``l`` of the stacked leaves over one sequence, x [S, E] in the
    type of the residual stream (float32; bfloat16 under ``quant="bf16"``),
    which every intermediate result takes too."""
    p = {k: v[l] for k, v in stack.items()}
    s, d = x.shape[0], head_dim

    def mm(a, w):
        return _mm(a, w, quant).astype(x.dtype)

    def dots(spec, a, b):
        return jnp.einsum(spec, a, b, precision="highest", preferred_element_type=F32)

    h = _rms(x, p["ln1"], eps)
    wq, wk, wv = jnp.split(p["wqkv"], [heads * d, (heads + kv_heads) * d], axis=1)
    q = _rope(mm(h, wq).reshape(s, heads, d), theta)
    k = _rope(mm(h, wk).reshape(s, kv_heads, d), theta)
    v = mm(h, wv).reshape(s, kv_heads, d)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    sc = dots("qhd,khd->hqk", q, k) * d ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    att = dots("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1).astype(x.dtype), v).astype(x.dtype)
    a = x + _rms(mm(att.reshape(s, heads * d), p["wo"]), p["ln2"], eps)
    h = _rms(a, p["ln3"], eps)
    mlp = mm(jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wu"]), p["wd"])
    return a + _rms(mlp, p["ln4"], eps)


@functools.lru_cache(maxsize=None)
def _jit_layer(quant, **dims):
    return jax.jit(functools.partial(layer_forward, quant=quant, **dims))


@functools.lru_cache(maxsize=None)
def _jit_norm(eps):
    return jax.jit(lambda x, w: _rms(x, w, eps))


@functools.lru_cache(maxsize=None)
def _jit_head(quant):
    return jax.jit(lambda head_w, x, rows: _mm(x[rows], head_w, quant))


def _dims(cfg):
    return dict(heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
                theta=float(cfg["rope_theta"]))


def pass_states(weights, cfg, ids, quant=None):
    """[x_1 .. x_R], each [S, E] float32 (bfloat16 under ``quant="bf16"``):
    every pass's result after the final norm, the layers one jitted call each."""
    layer = _jit_layer(quant, **_dims(cfg))
    norm = _jit_norm(cfg["rms_norm_eps"])
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(BF16 if quant == "bf16" else F32)
    out = []
    for _ in range(cfg["total_ut_steps"]):
        for l in range(cfg["num_hidden_layers"]):
            x = layer(weights["layers"], jnp.asarray(l, jnp.int32), x)
        x = norm(x, weights["norm"])
        out.append(x)
    return out


LENGTHS = 4            # a sequence is cut to one of this many lengths


def _cut(ids, rows):
    """``ids`` without the tail that no row of ``rows`` can see (causal), its
    length rounded up to a quarter of what was given, so that the jitted
    pieces compile for four lengths and not for every length."""
    step = -(-len(ids) // LENGTHS)
    need = int(max(rows)) + 1
    return ids[:min(len(ids), -(-need // step) * step)]


def logits_at(weights, cfg, ids, rows, quant=None, n_prompt=0):
    """Logits [len(rows), V] float32 of the last pass of the full forward
    over ``ids`` [S] at the positions ``rows``: row r predicts token r + 1.
    ``n_prompt`` is part of the references' common signature; nothing here
    reads it."""
    if float(cfg["early_exit_threshold"]) != 1.0:
        raise ValueError("the reference serves early_exit_threshold 1: every pass")
    x = pass_states(weights, cfg, _cut(ids, rows), quant)[-1]
    return _jit_head(quant)(weights["head"], x, jnp.asarray(rows, jnp.int32))


def pass_logits_at(weights, cfg, ids, rows, quant=None):
    """[R, len(rows), V]: the logits every pass would give."""
    rows = jnp.asarray(rows, jnp.int32)
    return jnp.stack([_jit_head(quant)(weights["head"], x, rows)
                      for x in pass_states(weights, cfg, ids, quant)])


def exit_probabilities(weights, cfg, ids, quant=None):
    """(lambda [R, S], p [R, S]) float32: the gate after each pass and the
    exit distribution, the last pass taking what is left."""
    w, b = weights["gate_w"].astype(F32), weights["gate_b"].astype(F32)
    lam = jnp.stack([jax.nn.sigmoid(jnp.matmul(x, w, precision="highest") + b)
                     for x in pass_states(weights, cfg, ids, quant)])
    p, stay = [], jnp.ones_like(lam[0])
    for r in range(lam.shape[0] - 1):
        p.append(lam[r] * stay)
        stay = stay * (1.0 - lam[r])
    return lam, jnp.stack(p + [stay])


def _witness(argv=None):
    """One line a lower precision: this reference in ``--low`` against itself
    in float32 on ``--sequences`` seeded sequences of the cell's lengths,
    padded as the check pads them; the lower precision's best token at every
    position past the prompt plays the served token."""
    import argparse
    import json

    import numpy as np

    from benchmark.harness import loader

    ap = argparse.ArgumentParser(description=_witness.__doc__)
    ap.add_argument("--config", default="ouro-2.6b.serve1")
    ap.add_argument("--root", default=loader.ROOT, help="where benchmark/configs/ lies")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sequences", type=int, default=10)
    ap.add_argument("--low", nargs="+", default=["bf16", "int8"])
    args = ap.parse_args(argv)
    with open(f"{args.root}/benchmark/configs/{args.config}.json") as f:
        cfg = json.load(f)
    weights = loader.load_module("families", cfg["family"]).make_weights(cfg, args.seed)
    rng = np.random.default_rng([args.seed, 7919])
    pad_to = int(cfg["check"]["pad_to"])
    gaps = {low: [] for low in args.low}
    for _ in range(args.sequences):
        n_prompt, new = (int(rng.integers(pad_to // 12, pad_to // 3 + 1)),
                         int(rng.integers(pad_to // 4, pad_to * 7 // 12 + 1)))
        ids = np.zeros(max(pad_to, n_prompt + new), np.int32)
        ids[:n_prompt + new] = rng.integers(1, cfg["vocab_size"], n_prompt + new)
        rows = np.arange(n_prompt - 1, n_prompt + new - 1)
        want = np.asarray(logits_at(weights, cfg, ids, rows))
        for low in args.low:
            token = np.asarray(logits_at(weights, cfg, ids, rows, quant=low)).argmax(-1)
            gaps[low].append(want.max(-1) - want[np.arange(len(rows)), token])
    for low, per_sequence in gaps.items():
        g = np.concatenate(per_sequence)
        print(json.dumps({"witness": low, "against": "float32", "config": args.config,
                          "seed": args.seed, "tokens": len(g), "mean_gap_nats": float(g.mean()),
                          "max_gap_nats": float(g.max()), "p99": float(np.quantile(g, 0.99)),
                          "argmax_agree": float((g == 0).mean())}), flush=True)


if __name__ == "__main__":
    _witness()
