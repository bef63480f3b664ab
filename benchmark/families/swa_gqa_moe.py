"""How a configuration of the ``swa_gqa_moe`` family (grouped-query attention
layers of two kinds by a published layout, global without position encoding and
sliding-window with RoPE, every feed-forward 64 ReGLU experts under a router that
reads the attention's input: SmallThinker-21BA3B-Instruct) is built: weights
made by the benchmark on the device from the seed, a layer a jitted call, in the
type they are served in; then the program's own ``SmallThinkerForCausalLM``
given them, as benchmark/families/mla_moe.py does for its family (whose seed
key this is).

The configuration file keeps the published key names: ``moe_num_primary_experts``
is the router's width AND the experts held (``experts_held`` [lo, hi) of them
where a file states a share; all where it states none);
``sliding_window_layout`` and ``rope_layout`` the published lists whole, of
which the first ``num_hidden_layers`` entries are built.

Drawn as benchmark/families/conv_gqa_moe.make_weights draws: matrices normal
with standard deviation fan_in**-0.5, the embedding table unit normal (the head
is its own matrix: ``tie_word_embeddings`` false), gains one.

The weights belong to the benchmark, not to the program: the plain reference
(benchmark/references/swa_gqa_moe.py) reads the same arrays and shares nothing
else with the program."""
from benchmark.harness import loader

seed_key = loader.load_module("families", "mla_moe").seed_key


def held_range(cfg):
    lo, hi = cfg.get("experts_held", (0, cfg["moe_num_primary_experts"]))
    return int(lo), int(hi)


def leaf_shapes(cfg):
    """({leaf: shape} of a layer, of the leaves outside the layers).  Linear
    weights are [in, out], as ``x @ w``; the held experts are stacked on a
    leading axis."""
    e, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    fm = cfg["moe_ffn_hidden_size"]
    lo, hi = held_range(cfg)
    layer = {"ln_in": (e,), "ln_post": (e,), "wq": (e, h), "wk": (e, kv), "wv": (e, kv),
             "wo": (h, e), "router": (e, cfg["moe_num_primary_experts"]),
             "eg": (hi - lo, e, fm), "eu": (hi - lo, e, fm), "ed": (hi - lo, fm, e)}
    return layer, {"embed": (v, e), "norm": (e,), "head": (e, v)}


def make_weights(cfg, seed):
    """{"embed", "norm", "head", "layers": [{leaf: array}]} in the
    configuration's ``torch_dtype``.  One jitted call a layer, so that the
    float32 draws of one layer are all that is held beside the result."""
    import jax
    import jax.numpy as jnp

    layer, outer = leaf_shapes(cfg)
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
    out = group(outer)(key)
    make = group(layer)
    out["layers"] = [make(jax.random.fold_in(key, 1000 + l))
                     for l in range(cfg["num_hidden_layers"])]
    return out


def model_config(cfg, **overrides):
    from paddle_tpu.models import SmallThinkerConfig

    keys = ("vocab_size", "hidden_size", "head_dim", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "moe_ffn_hidden_size",
            "moe_num_primary_experts", "moe_num_active_primary_experts",
            "moe_primary_router_apply_softmax", "norm_topk_prob", "sliding_window_size",
            "sliding_window_layout", "rope_layout", "rope_theta", "rope_scaling",
            "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings")
    kw = {k: cfg[k] for k in keys}
    kw.update(experts_held=held_range(cfg), dtype=cfg.get("torch_dtype", "bfloat16"))
    kw.update(overrides)
    return SmallThinkerConfig(**kw)


def params_of(model):
    """The program's parameters in the weights' structure."""
    net = model.model
    return {"embed": net.embed_tokens.weight, "norm": net.norm.weight,
            "head": model.lm_head.weight, "layers": [layer.leaves() for layer in net.layers]}


def build_model(cfg, **overrides):
    """The program's own model with abstract parameters; ``assign`` gives
    every one its value."""
    import paddle_tpu as P
    from paddle_tpu.models import SmallThinkerForCausalLM

    with P.LazyGuard():
        return SmallThinkerForCausalLM(model_config(cfg, **overrides))


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
