"""The plain reference of the dense Llama-shaped family: RMSNorm, rotary
embedding (rotate-half), grouped-query causal attention, SwiGLU, untied head;
for training the mean next-token cross-entropy, its gradient and AdamW.
Straightforward jax.numpy in float32 under ``highest`` matmul precision: no
kernel, no cache, no batching tricks, and nothing imported from the program.

It takes the benchmark's weights (benchmark/families/llama_dense.make_weights:
bf16 arrays, matrices [in, out]) and up-casts one layer at a time, so that it
fits beside them. ``cfg`` is the configuration file's dict.

``quant`` puts the same forward into a lower precision, for the control:
"int8" rounds every matrix (per output column) and every matmul input (per
token) to 8-bit integers, symmetric, as a W8A8 deployment would; "int8-kv"
rounds only the cached keys and values, as an int8 KV cache would (one scale
for each kv-head of a sequence, taken over its prompt and kept while it
decodes; a token's own key and value, and the whole of a one-shot prefill,
are attended before they are rounded)."""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, H, D]; positions 0..S-1; halves rotated (the HF convention)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _q8(x, axis):
    """Round to 8-bit integers, symmetric, one scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, quant):
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    elif quant not in (None, "int8-kv"):
        raise ValueError(f"unknown lower precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def _cached8(x, n_prompt):
    """x: [S, KV, D] keys or values as an int8 cache hands them back: one
    scale for each kv-head, the largest magnitude among the first
    ``n_prompt`` positions over 127; what a later token adds is clipped."""
    in_prompt = (jnp.arange(x.shape[0]) < n_prompt)[:, None, None]
    scale = jnp.max(jnp.where(in_prompt, jnp.abs(x), 0.0), axis=(0, 2), keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-6 / 127.0)
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale


def layer_forward(p, x, n_prompt=0, *, heads, kv_heads, eps, theta, quant=None):
    """One decoder layer over one sequence. x: [S, E] float32; p: the
    layer's leaves in any float type. ``n_prompt`` (read under "int8-kv"
    only) is where the prompt ends and decoding starts."""
    p = {k: v.astype(F32) for k, v in p.items()}
    s, e = x.shape
    d = p["wq"].shape[1] // heads
    h = _rms(x, p["ln1"], eps)
    q = _rope(_mm(h, p["wq"], quant).reshape(s, heads, d), theta)
    k = _rope(_mm(h, p["wk"], quant).reshape(s, kv_heads, d), theta)
    v = _mm(h, p["wv"], quant).reshape(s, kv_heads, d)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    if quant == "int8-kv":
        # a decoding token reads the earlier keys and values from the cache
        pos = jnp.arange(s)
        cached = ((pos[:, None] >= n_prompt) & (pos[None, :] < pos[:, None]))[None]
        k8, v8 = _cached8(k, n_prompt), _cached8(v, n_prompt)
        scores8 = jnp.einsum("qhd,khd->hqk", q, k8, precision="highest") * d ** -0.5
        scores = jnp.where(cached, scores8, scores)
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    if quant == "int8-kv":
        att = (jnp.einsum("hqk,khd->qhd", jnp.where(cached, 0.0, probs), v, precision="highest")
               + jnp.einsum("hqk,khd->qhd", jnp.where(cached, probs, 0.0), v8,
                            precision="highest")).reshape(s, heads * d)
    else:
        att = jnp.einsum("hqk,khd->qhd", probs, v, precision="highest").reshape(s, heads * d)
    x = x + _mm(att, p["wo"], quant)
    h = _rms(x, p["ln2"], eps)
    mlp = jax.nn.silu(_mm(h, p["wg"], quant)) * _mm(h, p["wu"], quant)
    return x + _mm(mlp, p["wd"], quant)


def _layer_kw(cfg):
    return dict(heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
                eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"])


@functools.lru_cache(maxsize=None)
def _jit_layer(heads, kv_heads, eps, theta, quant):
    return jax.jit(functools.partial(layer_forward, heads=heads, kv_heads=kv_heads,
                                     eps=eps, theta=theta, quant=quant))


@functools.lru_cache(maxsize=None)
def _jit_head(eps, quant):
    def head(norm_w, head_w, x, rows):
        h = _rms(x[rows], norm_w.astype(F32), eps)
        return _mm(h, head_w.astype(F32), quant)
    return jax.jit(head)


def logits_at(weights, cfg, ids, rows, quant=None, n_prompt=0):
    """Logits [len(rows), V] float32 of the full forward over ``ids`` [S] at
    the positions ``rows``: row r predicts token r + 1. ``n_prompt``: the
    prompt's length, which only the "int8-kv" control reads."""
    layer = _jit_layer(quant=quant, **_layer_kw(cfg))
    x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(F32)
    n_prompt = jnp.asarray(n_prompt, jnp.int32)
    for p in weights["layers"]:
        x = layer(p, x, n_prompt)
    return _jit_head(cfg["rms_norm_eps"], quant)(
        weights["norm"], weights["head"], x, jnp.asarray(rows, jnp.int32))


# ------------------------------------------------------------------ training
def _loss_sum(norm_w, head_w, x, labels, eps):
    """Summed next-token cross-entropy of one sequence. x: [S, E]; row r is
    scored against labels[r + 1]."""
    logits = jnp.matmul(_rms(x[:-1], norm_w, eps), head_w, precision="highest")
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - tgt)


@functools.lru_cache(maxsize=None)
def _jit_train_parts(heads, kv_heads, eps, theta):
    fwd = functools.partial(layer_forward, heads=heads, kv_heads=kv_heads,
                            eps=eps, theta=theta)

    def layer_bwd(p, x, dy):
        _, vjp = jax.vjp(fwd, p, x)
        return vjp(dy)                      # (dp, dx)

    def head_bwd(norm_w, head_w, x, labels, scale):
        loss, (dn, dh, dx) = jax.value_and_grad(_loss_sum, argnums=(0, 1, 2))(
            norm_w, head_w, x, labels, eps)
        return loss, dn * scale, dh * scale, dx * scale

    return jax.jit(fwd), jax.jit(layer_bwd), jax.jit(head_bwd)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(p, m, v, g, lr, b1, b2, eps, wd, t):
    """One leaf of AdamW (Loshchilov & Hutter): decay decoupled from the
    gradient, moments bias-corrected."""
    p = p * (1.0 - lr * wd)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    p = p - lr * (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
    return p, m, v


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


def train_steps(make_weights, cfg, batches, *, lr, beta1, beta2, epsilon, weight_decay,
                state_dtype=None):
    """Follows the program's first steps: from ``make_weights()`` (up-cast to
    float32 masters), one AdamW step per batch in ``batches`` (each [B, S]
    int32). Returns the loss of each step, the norm of each leaf's first
    gradient, and the norm of each leaf's change after the last step, leaves
    in ``jax.tree_util.tree_leaves`` order of the weights.

    Sequence by sequence and layer by layer (a layer's forward is computed
    again inside its vjp), so the float32 copy, two moments and one layer's
    gradients are all that is held. ``make_weights`` is called a second time
    at the end, for the change against the initial values.

    ``state_dtype`` (the control: "bfloat16") rounds the masters and both
    moments to that type after every step, as an optimizer without float32
    state would hold them."""
    fwd, layer_bwd, head_bwd = _jit_train_parts(**_layer_kw(cfg))

    def up(w):
        out = w.astype(F32)
        if out is not w:
            w.delete()
        return out

    p = jax.tree_util.tree_map(up, make_weights())
    n_layers = len(p["layers"])
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_norms = [], None
    hyper = [jnp.asarray(x, F32) for x in (lr, beta1, beta2, epsilon, weight_decay)]

    for t, batch in enumerate(batches, start=1):
        batch = jnp.asarray(batch, jnp.int32)
        n_rows, seq = batch.shape
        scale = jnp.asarray(1.0 / (n_rows * (seq - 1)), F32)
        step = jnp.asarray(float(t), F32)
        norms = {}

        def update(where, key, grad, tag=None):
            """AdamW on one leaf, the moment its gradient is whole: no
            gradient outlives its leaf's update."""
            norms[(tag, key)] = _norm(grad)
            new = _adamw(where[0][key], where[1][key], where[2][key], grad, *hyper, step)
            if state_dtype is not None:
                new = tuple(x.astype(state_dtype).astype(F32) for x in new)
            where[0][key], where[1][key], where[2][key] = new

        # forward, keeping each layer's input of each sequence
        inputs, outs = [], []
        for r in range(n_rows):
            x = p["embed"][batch[r]]
            row = []
            for l in range(n_layers):
                row.append(x)
                x = fwd(p["layers"][l], x)
            inputs.append(row)
            outs.append(x)
        # head and loss
        loss = jnp.zeros((), F32)
        g_norm, g_head = jnp.zeros_like(p["norm"]), jnp.zeros_like(p["head"])
        dxs = []
        for r in range(n_rows):
            ls, dn, dh, dx = head_bwd(p["norm"], p["head"], outs[r], batch[r], scale)
            loss, g_norm, g_head = loss + ls, g_norm + dn, g_head + dh
            dxs.append(dx)
        del outs, dn, dh
        losses.append(float(loss * scale))
        top = (p, m, v)
        update(top, "norm", g_norm)
        update(top, "head", g_head)
        del g_norm, g_head
        for l in reversed(range(n_layers)):
            acc = None
            for r in range(n_rows):
                dp, dxs[r] = layer_bwd(p["layers"][l], inputs[r][l], dxs[r])
                acc = dp if acc is None else jax.tree_util.tree_map(jnp.add, acc, dp)
            del dp
            here = (p["layers"][l], m["layers"][l], v["layers"][l])
            for key in sorted(acc):
                update(here, key, acc.pop(key), l)
        g_embed = jnp.zeros_like(p["embed"])
        for r in range(n_rows):
            g_embed = g_embed.at[batch[r]].add(dxs[r])
        del inputs, dxs
        update(top, "embed", g_embed)
        del g_embed
        if first_norms is None:
            # in the order jax.tree_util.tree_leaves gives the leaves
            tree = {k: norms[(None, k)] for k in ("embed", "head", "norm")}
            tree["layers"] = [{k: norms[(l, k)] for k in p["layers"][l]}
                              for l in range(n_layers)]
            first_norms = [float(x) for x in jax.tree_util.tree_leaves(tree)]
    grad_norms = first_norms
    del m, v
    change_norms = [float(_norm(a - b.astype(F32))) for a, b in zip(
        jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(make_weights()))]
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change_norms}
