"""How a configuration of the ``conv_gqa_moe`` family (gated short
convolutions, a grouped-query attention layer every fourth, sigmoid experts
chosen on score + bias and no shared expert: LFM2-24B-A2B) is built: weights
made by the benchmark on the device from the seed, a layer a jitted call, in
the type they are served in; then the program's own ``Lfm2MoeForCausalLM``
given them, as benchmark/families/mla_moe.py does for its family (whose seed
key this is).

The configuration file keeps the published key names: ``num_experts`` is the
router's width AND the experts held (``experts_held`` [lo, hi) of them where a
file states a share; all of them where it states none), ``layer_types`` the
published pattern whole, of which the first ``num_hidden_layers`` are built.

Drawn: matrices normal with standard deviation fan_in**-0.5; the embedding
table, which is ALSO the head (``tie_word_embeddings``), as the head it is:
standard deviation hidden**-0.5, so that logits spread by about 1 (the first
RMSNorm rescales what the layers see).  Drawn unit normal, as the families
with a head of their own draw their tables, a tied table makes every token
predict ITSELF by sqrt(hidden) = 45 nats and every precision agree with every
other (the first chip run's gaps were all exactly 0, the controls' too: PERF.md
section 6, PR 38); gains one, the convolution's three taps a channel normal with standard
deviation 3**-0.5 (so that the two taps the STATE feeds carry two thirds of
the filter's energy and a trunk that loses its state is far from the
reference), and the router's ``expert_bias`` normal with standard deviation
0.01 in float32: published checkpoints carry a trained one whose job is to
BALANCE the experts' load; zero would leave the bias path idle and a large one
unbalances what it exists to balance (benchmark/families/mla_dsa_moe.py, PR 32).

The weights belong to the benchmark, not to the program: the plain reference
(benchmark/references/conv_gqa_moe.py) reads the same arrays and shares
nothing else with the program."""
from benchmark.harness import loader
from benchmark.harness.conv_moe_cost import head_dim, held_range

seed_key = loader.load_module("families", "mla_moe").seed_key

BIAS_STD = 0.01


def layer_types(cfg):
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def leaf_shapes(cfg):
    """({leaf: shape} of a conv operator, of an attention operator, of a dense
    feed-forward, of an expert one, of the leaves outside the layers); a
    layer's leaves are an operator's and a feed-forward's.  Linear weights
    are [in, out], as ``x @ w``; the held experts are stacked on a leading
    axis."""
    e, v, d = cfg["hidden_size"], cfg["vocab_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    lo, hi = held_range(cfg)
    norms = {"ln_op": (e,), "ln_ffn": (e,)}
    conv = dict(norms, w_in=(e, 3 * e), conv_k=(e, cfg["conv_L_cache"]), w_out=(e, e))
    attn = dict(norms, wq=(e, h), wk=(e, kv), wv=(e, kv), wo=(h, e), q_norm=(d,),
                k_norm=(d,))
    dense = {"wg": (e, f), "wu": (e, f), "wd": (f, e)}
    sparse = {"router": (e, cfg["num_experts"]), "router_bias": (cfg["num_experts"],),
              "eg": (hi - lo, e, fm), "eu": (hi - lo, e, fm), "ed": (hi - lo, fm, e)}
    outer = {"embed": (v, e), "norm": (e,)}
    if not cfg.get("tie_word_embeddings", True):
        outer["head"] = (e, v)
    return conv, attn, dense, sparse, outer


def make_weights(cfg, seed):
    """{"embed", "norm", "layers": [{leaf: array}]} (and "head" where the
    configuration unties it), in the configuration's ``torch_dtype`` but for
    ``router_bias`` (float32).  One jitted call a kind of layer, so that the
    float32 draws of one layer are all that is held beside the result."""
    import jax
    import jax.numpy as jnp

    conv, attn, dense, sparse, outer = leaf_shapes(cfg)
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))

    def leaf(key, name, shape):
        if name == "router_bias":
            return jax.random.normal(key, shape, jnp.float32) * BIAS_STD
        if len(shape) == 1:
            return jnp.ones(shape, dt)
        std = shape[-1 if name in ("embed", "conv_k") else -2] ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def group(shapes):
        return jax.jit(lambda key: {name: leaf(jax.random.fold_in(key, j), name, shape)
                                    for j, (name, shape) in enumerate(sorted(shapes.items()))})

    key = seed_key(seed)
    make = {(op, ffn): group(dict(o, **f))
            for op, o in (("conv", conv), ("full_attention", attn))
            for ffn, f in (("dense", dense), ("sparse", sparse))}
    out = group(outer)(key)
    out["layers"] = [
        make[kind, "dense" if l < cfg["num_dense_layers"] else "sparse"](
            jax.random.fold_in(key, 1000 + l))
        for l, kind in enumerate(layer_types(cfg))]
    return out


def model_config(cfg, **overrides):
    from paddle_tpu.models import Lfm2MoeConfig

    if cfg.get("model_type", "lfm2_moe") != "lfm2_moe":
        raise ValueError("conv_gqa_moe builds lfm2_moe models")
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "num_dense_layers", "num_attention_heads",
            "num_key_value_heads", "conv_L_cache", "conv_bias", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
            "routed_scaling_factor", "max_position_embeddings", "norm_eps",
            "rope_parameters")
    kw = {k: cfg[k] for k in keys}
    kw.update(layer_types=layer_types(cfg), experts_held=held_range(cfg),
              tie_word_embeddings=cfg.get("tie_word_embeddings", True),
              dtype=cfg.get("torch_dtype", "bfloat16"))
    kw.update(overrides)
    return Lfm2MoeConfig(**kw)


def params_of(model):
    """The program's parameters in the weights' structure."""
    net = model.model
    out = {"embed": net.embed_tokens.weight, "norm": net.embedding_norm.weight,
           "layers": [layer.leaves() for layer in net.layers]}
    if model.lm_head is not None:
        out["head"] = model.lm_head.weight
    return out


def build_model(cfg, **overrides):
    """The program's own model with abstract parameters; ``assign`` gives
    every one its value."""
    import paddle_tpu as P
    from paddle_tpu.models import Lfm2MoeForCausalLM

    with P.LazyGuard():
        return Lfm2MoeForCausalLM(model_config(cfg, **overrides))


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
