"""How a configuration of the ``mla_dsa_moe`` family (latent attention under a
learned selection, two norms a layer, group-limited sigmoid routing with a
selection bias beside a shared expert, YaRN: DeepSeek-V3.2-Exp) is built:
weights made by the benchmark on the device from the seed, a layer a jitted
call, in the type they are served in; then the program's own
``DeepseekV32ForCausalLM`` given them, as benchmark/families/mla_moe.py does
for its family (whose seed key, held range and file conventions these are:
``n_routed_experts`` the experts HELD here, ``experts_held`` which of the
router's ``router_outputs``, ``vocab_size`` the slice of the vocabulary).

Drawn: matrices normal with standard deviation fan_in**-0.5 (so the indexer's
logits spread by about 1.9 and a selection changes the answer), the embedding
table unit normal, gains one, the LayerNorm's bias zero, and the router's
selection bias normal with standard deviation 0.01 in float32.  Published
checkpoints carry a trained one, whose job is to BALANCE the experts' load;
zero would leave the bias path idle, and a large one unbalances what it
exists to balance: at 0.1 a seed sent 64 to 570 of 512 tokens' picks to a
layer's 16 held experts, some experts none and some over a tile of 128, and
the cell's rate moved by 3 % from seed to seed with the tiles in use (42-54
of 64; PERF.md section 6, PR 32).  At 0.01 the bias still changes 0.8 of a
token's 8 picks and every held expert gets its 15-17 rows.

The weights belong to the benchmark, not to the program: the plain reference
(benchmark/references/mla_dsa_moe.py) reads the same arrays and shares nothing
else with the program."""
from benchmark.harness import loader

_BASE = loader.load_module("families", "mla_moe")
seed_key, held_range = _BASE.seed_key, _BASE.held_range

BIAS_STD = 0.01


def leaf_shapes(cfg):
    """({leaf: shape} of a dense layer, of an expert layer, of the leaves
    outside the layers).  Linear weights are [in, out], as ``x @ w``."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    h, n, r, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                   cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    j, d = cfg["index_n_heads"], cfg["index_head_dim"]
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    lo, hi = held_range(cfg)
    outputs = cfg.get("router_outputs", cfg["n_routed_experts"])
    attn = {"ln_in": (e,), "ln_post": (e,),
            "wq_a": (e, ql), "q_norm": (ql,), "wq_b": (ql, h * (n + r)),
            "wkv_a": (e, kl + r), "kv_norm": (kl,), "wkv_b": (kl, h * (n + vd)),
            "wo": (h * vd, e),
            "wiq": (ql, j * d), "wik": (e, d), "ik_norm_w": (d,), "ik_norm_b": (d,),
            "wiw": (e, j)}
    dense = dict(attn, wg=(e, f), wu=(e, f), wd=(f, e))
    sparse = dict(attn, router=(e, outputs), router_bias=(outputs,),
                  eg=(hi - lo, e, fm), eu=(hi - lo, e, fm), ed=(hi - lo, fm, e),
                  sg=(e, fm), su=(e, fm), sd=(fm, e))
    return dense, sparse, {"embed": (v, e), "norm": (e,), "head": (e, v)}


def make_weights(cfg, seed):
    """{"embed", "norm", "head", "layers": [{leaf: array}]} (and "mtp" where
    the configuration runs the next-token module), in the configuration's
    ``torch_dtype`` but for the selection bias (float32).  One jitted call a
    layer, so that the float32 draws of one layer are all that is held beside
    the result."""
    import jax
    import jax.numpy as jnp

    dense, sparse, outer = leaf_shapes(cfg)
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))

    def leaf(key, name, shape):
        if name == "router_bias":
            return jax.random.normal(key, shape, jnp.float32) * BIAS_STD
        if name == "ik_norm_b":
            return jnp.zeros(shape, dt)
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
    from paddle_tpu.models import DeepseekV32Config

    if cfg.get("model_type", "deepseek_v32") != "deepseek_v32":
        raise ValueError("mla_dsa_moe builds deepseek_v32 models")
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "first_k_dense_replace", "moe_layer_freq",
            "num_attention_heads", "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_n_heads",
            "index_head_dim", "index_topk", "n_shared_experts", "num_experts_per_tok",
            "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "scoring_func", "topk_method", "num_nextn_predict_layers",
            "max_position_embeddings", "rms_norm_eps", "rope_theta", "rope_scaling",
            "tie_word_embeddings", "attention_bias", "hidden_act", "ep_size")
    kw = {k: cfg[k] for k in keys}
    kw.update(n_routed_experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
              experts_held=held_range(cfg), dtype=cfg.get("torch_dtype", "bfloat16"))
    kw.update(overrides)
    return DeepseekV32Config(**kw)


def params_of(model):
    """The program's parameters in the weights' structure."""
    net = model.backbone
    out = {"embed": net.embed_tokens.weight, "norm": net.norm.weight,
           "head": model.lm_head.weight,
           "layers": [layer.leaves() for layer in net.layers]}
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
    from paddle_tpu.models import DeepseekV32ForCausalLM

    with P.LazyGuard():
        return DeepseekV32ForCausalLM(model_config(cfg, **overrides))


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
