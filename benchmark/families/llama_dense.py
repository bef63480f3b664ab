"""How a configuration of the dense Llama-shaped family is built: weights made
by the benchmark on the device, in one jitted call from the seed, in the type
they are served in; then the program's own ``LlamaForCausalLM`` given them.

The weights belong to the benchmark, not to the program: the plain reference
(benchmark/references/llama_dense.py) reads the same arrays and shares nothing
else with the program."""


def seed_key(seed):
    """A jax key from any whole number: --seed may pass 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf_shapes(cfg):
    """{leaf: shape} of one layer, and of the three leaves outside them.
    Linear weights are [in, out], as ``x @ w``."""
    e, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or e // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    layer = {"ln1": (e,), "wq": (e, q), "wk": (e, kv), "wv": (e, kv),
             "wo": (q, e), "ln2": (e,), "wg": (e, f), "wu": (e, f), "wd": (f, e)}
    return layer, {"embed": (v, e), "norm": (e,), "head": (e, v)}


def make_weights(cfg, seed):
    """{"embed", "norm", "head", "layers": [{leaf: array}]}: matrices normal
    with standard deviation fan_in**-0.5 (unit-variance activations), the
    embedding table unit normal, norms one, in the configuration's
    ``torch_dtype``. One jitted call."""
    import jax
    import jax.numpy as jnp

    layer, outer = leaf_shapes(cfg)
    n_layers = cfg["num_hidden_layers"]
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))

    def leaf(key, name, shape):
        if len(shape) == 1:
            return jnp.ones(shape, dt)
        std = 1.0 if name == "embed" else shape[0] ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(outer.items())):
            out[name] = leaf(jax.random.fold_in(key, i), name, shape)
        out["layers"] = []
        for l in range(n_layers):
            kl = jax.random.fold_in(key, 1000 + l)
            out["layers"].append(
                {name: leaf(jax.random.fold_in(kl, j), name, shape)
                 for j, (name, shape) in enumerate(sorted(layer.items()))})
        return out

    return jax.jit(make)(seed_key(seed))


def model_config(cfg, **overrides):
    from paddle_tpu.models import LlamaConfig

    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("sliding_window"):
        raise ValueError("llama_dense builds SwiGLU models with full attention only")
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    if d * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden/heads")
    kw = dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
              intermediate_size=cfg["intermediate_size"],
              num_hidden_layers=cfg["num_hidden_layers"],
              num_attention_heads=cfg["num_attention_heads"],
              num_key_value_heads=cfg["num_key_value_heads"],
              max_position_embeddings=cfg["max_position_embeddings"],
              rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
              tie_word_embeddings=cfg.get("tie_word_embeddings", False),
              dtype=cfg.get("torch_dtype", "bfloat16"))
    kw.update(overrides)
    return LlamaConfig(**kw)


def params_of(model):
    """The program's parameters in the weights' structure."""
    lm = model.llama
    out = {"embed": lm.embed_tokens.weight, "norm": lm.norm.weight,
           "head": model.lm_head.weight, "layers": []}
    for layer in lm.layers:
        a, m = layer.self_attn, layer.mlp
        out["layers"].append({
            "ln1": layer.input_layernorm.weight, "wq": a.q_proj.weight,
            "wk": a.k_proj.weight, "wv": a.v_proj.weight, "wo": a.o_proj.weight,
            "ln2": layer.post_attention_layernorm.weight,
            "wg": m.gate_proj.weight, "wu": m.up_proj.weight, "wd": m.down_proj.weight})
    return out


def build_model(cfg, **overrides):
    """The program's own model, as its constructor makes it (leaf by leaf,
    float32, then cast); ``assign`` then replaces every value."""
    from paddle_tpu.models import LlamaForCausalLM

    lcfg = model_config(cfg, **overrides)
    model = LlamaForCausalLM(lcfg)
    if lcfg.dtype == "bfloat16":
        model.bfloat16()
    return model


def assign(model, weights):
    """The benchmark's weights into the program's parameters, each placed as
    the parameter was (a mesh shards them here)."""
    import jax

    def put(p, w):
        if tuple(p._value.shape) != tuple(w.shape) or p._value.dtype != w.dtype:
            raise ValueError(f"weight {w.shape} {w.dtype} for a parameter "
                             f"{p._value.shape} {p._value.dtype}")
        p._value = jax.device_put(w, p._value.sharding)

    jax.tree_util.tree_map(put, params_of(model), weights,
                           is_leaf=lambda x: hasattr(x, "_value"))
