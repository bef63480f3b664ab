"""How a configuration of the ``parallel_swa_moe`` family (a PARALLEL block: one
LayerNorm a layer feeds grouped-query attention, four averaged shared experts
and sigmoid-routed experts, all three joining the residual together; attention
layers of two kinds by the published ``layer_types``, sliding-window with
interleaved RoPE and full without position encoding; a head that is the
embedding table: command-a-plus-05-2026) is built: weights made by the benchmark
on the device from the seed, a layer a jitted call, in the type they are served
in; then the program's own ``Cohere2MoeForCausalLM`` given them, as
benchmark/families/swa_gqa_moe.py does for its family (whose seed key this is).

The configuration file keeps the published key names: ``num_experts`` counts the
experts HELD here, ``experts_held`` says which ([lo, hi) of the router's
``router_outputs``), ``vocab_size`` the rows of the vocabulary's slice;
``layer_types`` is the published list whole, of which the first
``num_hidden_layers`` entries are built.

Drawn: matrices normal with standard deviation fan_in**-0.5, gains one;
``W_q`` alone ``SCORE_STD`` = 3 times wider, so that a query's scores spread by 3
and not by 1: at 1 the attention of 2-33 k keys is the mean of thousands of
random values, 0.02 a component beside the shared experts' 0.3, the model all
but a function of the current token, 99 % of served tokens agree with the
reference's best to the bit and neither a lower precision nor a forgotten
window moves the gaps (PERF.md section 6, PR 48: at 1 W8A8 read 0.6 of the
largest sound run, at 3 it reads 7.8 times it); at 3 a query reads a few keys,
as a trained head does, and attention adds to the residual what the shared
experts add.  The embedding table, which is ALSO the head (``tie_word_embeddings``), as the head it
is, standard deviation hidden**-0.5, so that logits spread by about 1 (every
layer's LayerNorm rescales what it reads).  Drawn unit normal a tied table makes
every token predict ITSELF by sqrt(hidden) = 64 nats and every precision agree
with every other (benchmark/families/conv_gqa_moe.py, PERF.md section 6, PR 38).  The four
shared experts lie side by side in ``sg``, ``su`` [E, 4F] and ``sd`` [4F, E]
(expert j is columns, and rows of ``sd``, [jF, (j + 1)F)): each down matrix's
fan-in is F.  The head is the table: ``embed`` is ONE leaf for both.

The weights belong to the benchmark, not to the program: the plain reference
(benchmark/references/parallel_swa_moe.py) reads the same arrays and shares
nothing else with the program."""
from benchmark.harness import loader

SCORE_STD = 3.0      # W_q's width over fan_in**-0.5: the spread of a query's scores (above)
seed_key = loader.load_module("families", "mla_moe").seed_key


def held_range(cfg):
    lo, hi = cfg.get("experts_held", (0, cfg["num_experts"]))
    return int(lo), int(hi)


def router_outputs(cfg) -> int:
    return cfg.get("router_outputs", cfg["num_experts"])


def leaf_shapes(cfg):
    """({leaf: shape} of a layer, of the leaves outside the layers).  Linear
    weights are [in, out], as ``x @ w``; the held experts are stacked on a
    leading axis, the shared ones side by side."""
    e, v, d, f = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    lo, hi = held_range(cfg)
    fs = cfg["num_shared_experts"] * f
    layer = {"ln": (e,), "wq": (e, h), "wk": (e, kv), "wv": (e, kv), "wo": (h, e),
             "router": (e, router_outputs(cfg)),
             "eg": (hi - lo, e, f), "eu": (hi - lo, e, f), "ed": (hi - lo, f, e),
             "sg": (e, fs), "su": (e, fs), "sd": (fs, e)}
    return layer, {"embed": (v, e), "norm": (e,)}


def make_weights(cfg, seed):
    """{"embed", "norm", "layers": [{leaf: array}]} in the configuration's
    ``torch_dtype``.  One jitted call a layer, so that the float32 draws of one
    layer are all that is held beside the result."""
    import jax
    import jax.numpy as jnp

    layer, outer = leaf_shapes(cfg)
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))

    def leaf(key, name, shape):
        if len(shape) == 1:
            return jnp.ones(shape, dt)
        fan_in = shape[-2] // cfg["num_shared_experts"] if name == "sd" else shape[-2]
        std = (shape[-1] if name == "embed" else fan_in) ** -0.5
        if name == "wq":
            std *= SCORE_STD
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
    from paddle_tpu.models import Cohere2MoeConfig

    if cfg.get("model_type", "cohere2_moe") != "cohere2_moe":
        raise ValueError("parallel_swa_moe builds cohere2_moe models")
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim", "layer_types",
            "sliding_window", "num_experts_per_tok", "num_shared_experts",
            "shared_expert_combination_strategy", "expert_selection_fn", "norm_topk_prob",
            "first_k_dense_replace", "logit_scale", "layer_norm_eps",
            "position_embedding_type", "rotary_pct", "rope_theta", "use_parallel_block",
            "use_qk_norm", "use_gated_activation", "hidden_act", "attention_bias",
            "tie_word_embeddings", "max_position_embeddings")
    kw = {k: cfg[k] for k in keys}
    kw.update(num_experts=router_outputs(cfg), experts_held=held_range(cfg),
              dtype=cfg.get("torch_dtype", "bfloat16"))
    kw.update(overrides)
    return Cohere2MoeConfig(**kw)


def params_of(model):
    """The program's parameters in the weights' structure: the tied table is
    ONE leaf."""
    net = model.model
    return {"embed": net.embed_tokens.weight, "norm": net.norm.weight,
            "layers": [layer.leaves() for layer in net.layers]}


def build_model(cfg, **overrides):
    """The program's own model with abstract parameters; ``assign`` gives
    every one its value."""
    import paddle_tpu as P
    from paddle_tpu.models import Cohere2MoeForCausalLM

    with P.LazyGuard():
        return Cohere2MoeForCausalLM(model_config(cfg, **overrides))


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
