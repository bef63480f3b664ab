"""How a configuration of the ``mla_moe`` family (latent attention, sandwich
norms, sigmoid-routed experts beside a shared one: openPangu-Ultra-MoE) is
built: weights made by the benchmark on the device from the seed, a layer a
jitted call, in the type they are served in; then the program's own
``PanguUltraMoEForCausalLM`` given them.

The configuration file states the chip's SHARE of its deployment:
``n_routed_experts`` is how many routed experts are held here,
``experts_held`` which ([lo, hi) of the router's ``router_outputs``, the
published count), ``vocab_size`` the slice of the vocabulary.  The model is
built inside ``paddle.LazyGuard`` (parameters abstract until ``assign``): its
own bf16 copy would not fit beside the benchmark's.

The weights belong to the benchmark, not to the program: the plain reference
(benchmark/references/mla_moe.py) reads the same arrays and shares nothing
else with the program."""


def seed_key(seed):
    """A jax key from any whole number: --seed may pass 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def held_range(cfg):
    lo, hi = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    return int(lo), int(hi)


def leaf_shapes(cfg):
    """({leaf: shape} of a dense layer, of an expert layer, of the leaves
    outside the layers).  Linear weights are [in, out], as ``x @ w``; the
    held experts are stacked on a leading axis."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    h, n, r, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                   cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    lo, hi = held_range(cfg)
    attn = {"ln_in": (e,), "ln_post_attn": (e,), "ln_pre_mlp": (e,), "ln_post_mlp": (e,),
            "wq_a": (e, ql), "q_norm": (ql,), "wq_b": (ql, h * (n + r)),
            "wkv_a": (e, kl + r), "kv_norm": (kl,), "wkv_b": (kl, h * (n + vd)),
            "wo": (h * vd, e)}
    dense = dict(attn, wg=(e, f), wu=(e, f), wd=(f, e))
    sparse = dict(attn, router=(e, cfg.get("router_outputs", cfg["n_routed_experts"])),
                  eg=(hi - lo, e, fm), eu=(hi - lo, e, fm), ed=(hi - lo, fm, e),
                  sg=(e, fm), su=(e, fm), sd=(fm, e))
    return dense, sparse, {"embed": (v, e), "norm": (e,), "head": (e, v)}


def make_weights(cfg, seed):
    """{"embed", "norm", "head", "layers": [{leaf: array}]} (and "mtp" where
    the configuration runs the next-token module): matrices normal with
    standard deviation fan_in**-0.5, the embedding table unit normal, gains
    one, in the configuration's ``torch_dtype``.  One jitted call a layer, so
    that the float32 draws of one layer are all that is held beside the
    result."""
    import jax
    import jax.numpy as jnp

    dense, sparse, outer = leaf_shapes(cfg)
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))

    def leaf(key, name, shape):
        if len(shape) == 1:
            return jnp.ones(shape, dt)
        std = 1.0 if name == "embed" else shape[-2] ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def group(shapes):
        return jax.jit(lambda key: {name: leaf(jax.random.fold_in(key, j), name, shape)
                                    for j, (name, shape) in enumerate(sorted(shapes.items()))})

    key = seed_key(seed)
    make = {"dense": group(dense), "sparse": group(sparse)}
    out = group(outer)(key)
    first = cfg["first_k_dense_replace"]
    out["layers"] = [make["dense" if l < first else "sparse"](jax.random.fold_in(key, 1000 + l))
                     for l in range(cfg["num_hidden_layers"])]
    if cfg.get("num_nextn_predict_layers", 0):
        e = cfg["hidden_size"]
        mtp = group({"hnorm": (e,), "enorm": (e,), "norm": (e,), "proj": (2 * e, e)})(
            jax.random.fold_in(key, 5000))
        mtp["layer"] = make["sparse"](jax.random.fold_in(key, 5001))
        out["mtp"] = mtp
    return out


def model_config(cfg, **overrides):
    from paddle_tpu.models import PanguUltraMoEConfig

    if cfg.get("model_type", "pangu_ultra_moe") != "pangu_ultra_moe":
        raise ValueError("mla_moe builds pangu_ultra_moe models")
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
            "num_key_value_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_shared_experts", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "sandwich_norm",
            "num_nextn_predict_layers", "max_position_embeddings", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings", "attention_bias", "hidden_act")
    kw = {k: cfg[k] for k in keys}
    kw.update(n_routed_experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
              experts_held=held_range(cfg), dtype=cfg.get("torch_dtype", "bfloat16"))
    kw.update(overrides)
    return PanguUltraMoEConfig(**kw)


def params_of(model):
    """The program's parameters in the weights' structure."""
    out = {"embed": model.pangu.embed_tokens.weight, "norm": model.pangu.norm.weight,
           "head": model.lm_head.weight,
           "layers": [layer.leaves() for layer in model.pangu.layers]}
    if model.mtp is not None:
        m = model.mtp
        out["mtp"] = {"hnorm": m.hnorm.weight, "enorm": m.enorm.weight,
                      "norm": m.norm.weight, "proj": m.eh_proj.weight,
                      "layer": m.block.leaves()}
    return out


def build_model(cfg, **overrides):
    """The program's own model with abstract parameters; ``assign`` gives
    every one its value."""
    import paddle_tpu as P
    from paddle_tpu.models import PanguUltraMoEForCausalLM

    with P.LazyGuard():
        return PanguUltraMoEForCausalLM(model_config(cfg, **overrides))


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
