"""How a configuration of the ``looped_dense`` family (Ouro: one stack of
sandwich-normed dense layers run ``total_ut_steps`` times over the same
weights) is built: weights made by the benchmark on the device from the seed
in ONE jitted call, in the type they are served in, the layers' leaves
stacked on a leading depth axis as the program keeps them; then the program's
own ``OuroForCausalLM`` given them.  The model is built inside
``paddle.LazyGuard`` (parameters abstract until ``assign``), so that no
float32 copy of it is ever made.

The weights belong to the benchmark, not to the program: the plain reference
(benchmark/references/looped_dense.py) reads the same arrays and shares
nothing else with the program."""


def seed_key(seed):
    """A jax key from any whole number: --seed may pass 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf_shapes(cfg):
    """({leaf: shape} of the stacked layers, of the leaves outside them).
    Linear weights are [in, out], as ``x @ w``."""
    L, e, f, v = (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["vocab_size"])
    h = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    # wqkv: the published q_proj | k_proj | v_proj side by side, as the
    # program keeps them
    layers = {"ln1": (L, e), "ln2": (L, e), "ln3": (L, e), "ln4": (L, e),
              "wqkv": (L, e, h + 2 * kv), "wo": (L, h, e),
              "wg": (L, e, f), "wu": (L, e, f), "wd": (L, f, e)}
    return layers, {"embed": (v, e), "norm": (e,), "head": (e, v),
                    "gate_w": (e,), "gate_b": (1,)}


def make_weights(cfg, seed):
    """{"embed", "norm", "head", "gate_w", "gate_b", "layers": {leaf:
    [depth, ...]}}: matrices normal with standard deviation fan_in**-0.5 (the
    gate's too, so its logit is of unit size on a normed state), the
    embedding table unit normal, gains one, the gate's bias zero, in the
    configuration's ``torch_dtype``."""
    import jax
    import jax.numpy as jnp

    layers, outer = leaf_shapes(cfg)
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))

    def leaf(key, name, shape):
        if name == "gate_b":
            return jnp.zeros(shape, dt)
        if name.startswith(("ln", "norm")):
            return jnp.ones(shape, dt)
        fan_in = shape[-2] if len(shape) > 1 else shape[0]       # the gate is a vector
        std = 1.0 if name == "embed" else fan_in ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def group(key, shapes):
        return {name: leaf(jax.random.fold_in(key, j), name, shape)
                for j, (name, shape) in enumerate(sorted(shapes.items()))}

    @jax.jit
    def make(key):
        out = group(key, outer)
        out["layers"] = group(jax.random.fold_in(key, 1000), layers)
        return out

    return make(seed_key(seed))


def model_config(cfg, **overrides):
    from paddle_tpu.models import OuroConfig

    if cfg.get("model_type", "ouro") != "ouro":
        raise ValueError("looped_dense builds ouro models")
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "max_position_embeddings", "rms_norm_eps", "rope_theta", "total_ut_steps",
            "early_exit_threshold", "tie_word_embeddings", "hidden_act")
    kw = {k: cfg[k] for k in keys}
    kw.update(dtype=cfg.get("torch_dtype", "bfloat16"))
    kw.update(overrides)
    return OuroConfig(**kw)


def params_of(model):
    """The program's parameters in the weights' structure."""
    return model.leaves()


def build_model(cfg, **overrides):
    """The program's own model with abstract parameters; ``assign`` gives
    every one its value."""
    import paddle_tpu as P
    from paddle_tpu.models import OuroForCausalLM

    with P.LazyGuard():
        return OuroForCausalLM(model_config(cfg, **overrides))


def assign(model, weights):
    """The benchmark's weights into the program's parameters."""
    import jax

    def put(p, w):
        if tuple(p._value.shape) != tuple(w.shape) or p._value.dtype != w.dtype:
            raise ValueError(f"weight {w.shape} {w.dtype} for a parameter "
                             f"{p._value.shape} {p._value.dtype}")
        p._value = w

    jax.tree_util.tree_map(put, params_of(model), weights,
                           is_leaf=lambda x: hasattr(x, "_value"))
