"""What the tests of the served families share, and no test file: each
family's tiny configuration and the model built from it, the engine geometry
the family files serve it under, THE function that lowers an engine's four
programs to text, the recorder of what its launches harvest, and the pins of
tests/data/program_pins.json.

The pins: sha256 (first 16 hex digits) of each family's ``step`` / ``mega`` /
``mixed`` / ``spec`` program as it lowers on the CPU at the tiny geometry,
and each family's cache specification and program key.  They are what lets a
PR say "no other family's program moved" and be held to it
(tests/test_program_pins.py).  A PR that moves a text on purpose says so in
CHANGES.md and records anew:

    python tests/programs.py            # prints family, kind, old and new of each pin that moved
    python tests/programs.py --write    # and writes the file
"""
import hashlib
import json
import os
import sys

if __name__ == "__main__":      # as a script: the repo on the path, the suite's platform
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.distributed.topology import set_hybrid_communicate_group
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.serving import control_layout
from paddle_tpu.models import LlamaForCausalLM, llama_tiny

from benchmark.harness import loader

PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "program_pins.json")
KINDS = ("step", "mega", "mixed", "spec")
ENGINE = dict(max_batch_size=4, max_seq_len=96, block_size=8, token_budget=32, megastep_k=4)

# family -> its module under benchmark/families (llama is built from its class)
MODULES = {"pangu": "mla_moe", "ouro": "looped_dense", "deepseek": "mla_dsa_moe",
           "lfm2": "conv_gqa_moe", "smallthinker": "swa_gqa_moe",
           "cohere2": "parallel_swa_moe"}

# LFM2: two leading dense conv layers and one period of the pattern (attention,
# conv, conv, conv): 5 conv layers keep state, 1 attention layer keeps blocks
LFM2_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
              "conv"]
# DeepSeek-V3.2: YaRN by 4 over 16
_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1, "mscale_all_dim": 1,
         "original_max_position_embeddings": 16, "type": "yarn"}
TINY = {
    # a share of a deployment: 16 routed experts a layer, this chip holds [4, 8)
    "pangu": dict(
        vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
        num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=4, router_outputs=16,
        experts_held=[4, 8], n_shared_experts=1, num_experts_per_tok=4, norm_topk_prob=True,
        routed_scaling_factor=2.5, sandwich_norm=True, num_nextn_predict_layers=0,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attention_bias=False, hidden_act="silu",
        torch_dtype="float32"),
    "ouro": dict(
        vocab_size=256, hidden_size=64, intermediate_size=160, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
        total_ut_steps=4, early_exit_threshold=1, tie_word_embeddings=False,
        hidden_act="silu", model_type="ouro", torch_dtype="float32"),
    # 16 routed experts a layer in 4 groups of which 2 stay, this chip holds
    # [4, 12); a selection of 8 positions
    "deepseek": dict(
        vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
        num_hidden_layers=3, first_k_dense_replace=1, moe_layer_freq=1, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, index_n_heads=4, index_head_dim=16, index_topk=8,
        n_routed_experts=8, router_outputs=16, experts_held=[4, 12], n_shared_experts=1,
        num_experts_per_tok=4, n_group=4, topk_group=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, scoring_func="sigmoid", topk_method="noaux_tc",
        num_nextn_predict_layers=0, max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, rope_scaling=_YARN, tie_word_embeddings=False,
        attention_bias=False, hidden_act="silu", ep_size=1, model_type="deepseek_v32",
        torch_dtype="float32"),
    "lfm2": dict(
        vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
        num_hidden_layers=6, num_dense_layers=2, layer_types=LFM2_TYPES,
        num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3, conv_bias=False,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1, max_position_embeddings=256, norm_eps=1e-5,
        rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
        model_type="lfm2_moe", torch_dtype="float32"),
    # SmallThinker: one period of the layout (global without rope, then three
    # window layers with it), a window of 24 positions = 3 blocks of ENGINE's 8
    "smallthinker": dict(
        vocab_size=256, hidden_size=64, head_dim=16, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, moe_ffn_hidden_size=32,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True, sliding_window_size=24,
        sliding_window_layout=[0, 1, 1, 1, 0, 1, 1, 1], rope_layout=[0, 1, 1, 1, 0, 1, 1, 1],
        rope_theta=10000.0, rope_scaling=None, max_position_embeddings=256,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        model_name="smallthinker_21b_instruct", torch_dtype="float32"),
    # Command A+: one period of the layout (three window layers with interleaved
    # rope, then a global one without), a window of 24 positions = 3 blocks of
    # ENGINE's 8; a share of a deployment: 16 routed experts a layer of which this
    # chip holds [4, 12), four shared experts averaged, a tied head
    "cohere2": dict(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        layer_types=["sliding_attention"] * 3 + ["full_attention"], sliding_window=24,
        num_experts=8, router_outputs=16, experts_held=[4, 12], num_experts_per_tok=4,
        num_shared_experts=4, shared_expert_combination_strategy="average",
        expert_selection_fn="sigmoid", norm_topk_prob=True, first_k_dense_replace=0,
        logit_scale=1, layer_norm_eps=1e-5, position_embedding_type="rope_gptj",
        rotary_pct=1, rope_theta=10000.0, use_parallel_block=True, use_qk_norm=False,
        use_gated_activation=True, hidden_act="silu", attention_bias=False,
        tie_word_embeddings=True, max_position_embeddings=256,
        model_type="cohere2_moe", torch_dtype="float32"),
}


def build(family, cfg=None, seed=7):
    """(model in eval mode, its weights as the family's ``make_weights`` gives
    them from ``seed``) of ``family`` at ``cfg`` (its ``TINY`` by default)."""
    set_hybrid_communicate_group(None)
    module = loader.load_module("families", MODULES[family])
    cfg = TINY[family] if cfg is None else cfg
    weights = module.make_weights(cfg, seed)
    model = module.build_model(cfg)
    module.assign(model, weights)
    return model.eval(), weights


def pinned_model(family):
    """The model whose programs are pinned: ``TINY`` of the family, but
    openPangu the sub-tiny of tests/benchmark/fixture_mla_moe and Llama
    ``llama_tiny``."""
    set_hybrid_communicate_group(None)
    P.seed(0)
    if family == "llama":
        return LlamaForCausalLM(llama_tiny()).eval()
    if family == "pangu":
        fixture = os.path.join(loader.ROOT, "tests", "benchmark", "fixture_mla_moe")
        return build(family, loader.load_cell("tiny.mla-moe.docs", root=fixture).config)[0]
    return build(family)[0]


def pinned_engine(family):
    """Its engine with every program it can lower (a model with state a slot
    refuses speculation: its ``spec`` program lowers at no drafts)."""
    spec_k = 0 if family in ("lfm2", "smallthinker", "cohere2") else 2
    return ServingEngine(pinned_model(family), spec_k=spec_k, **ENGINE)


def prompts(lens, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).tolist() for n in lens]


def lowered(eng, debug_info, kinds=KINDS):
    """{kind: the lowered text of the engine's program of that kind}."""
    B, T, P_, C, K = eng.B, eng.T, eng.P, eng.pc, eng.megastep_k

    def block(kind, n=0):        # the ONE control array a launch sends up (ISSUE 35)
        return jax.ShapeDtypeStruct(
            (control_layout(kind, B, P_, n, len(eng.kinds)).size,), jnp.int32)

    head = (eng._weights, eng.program_caches(), eng._rope)
    low = {
        "step": lambda: eng._step_fn.lower(*head, block("step", T), None, mq=T),
        "mega": lambda: eng._build_megastep().lower(*head, block("mega"), None, K=K),
        "mixed": lambda: eng._build_mixed_megastep().lower(
            *head, block("mixed", K * C), None, K=K),
        "spec": lambda: eng._build_spec_verify().lower(
            *head, block("spec", eng.spec_k), None),
    }
    return {k: low[k]().as_text(debug_info=debug_info) for k in kinds}


def text_pin(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def spec_pin(model):
    """(cache layers, the pool's arrays, state a slot, the program key of an
    engine of ``ENGINE``) in the types JSON keeps."""
    spec = model.serving_cache_spec()
    eng = ServingEngine(model, **ENGINE)
    assert len(eng.program_caches()) == len(spec.arrays) + len(spec.slot_state)
    return json.loads(json.dumps({
        "layers": spec.layers, "arrays": [n for n, _ in spec.arrays],
        "slot_state": spec.slot_state, "program_key": eng._program_key()}))


def harvests(eng):
    """[(kind of the launch, its attributes, the attributes of its
    ``engine.harvest`` span)], filled as the engine runs."""
    seen, launches = [], []
    launch, phase = eng._launch_phase, eng._phase

    def launched(kind, *a, **kw):
        launches.append(kind)
        return launch(kind, *a, **kw)

    def entered(name, **attrs):
        if name == "launch":
            launches.append(attrs)
        if name == "harvest":
            seen.append((launches[-2], launches[-1], attrs))
        return phase(name, **attrs)

    eng._launch_phase, eng._phase = launched, entered
    return seen


def recorded():
    with open(PINS) as f:
        return json.load(f)


def dumped(pins):
    """The file's text: a line a family in each section, so a diff shows who moved."""
    def section(rows):
        return "{\n" + ",\n".join(f"  {json.dumps(k)}: {json.dumps(v)}"
                                  for k, v in rows.items()) + "\n }"
    return (f'{{\n "jax": {json.dumps(pins["jax"])},\n "texts": {section(pins["texts"])},\n'
            f' "specs": {section(pins["specs"])}\n}}\n')


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--write", action="store_true", help="write what was found to " + PINS)
    args = ap.parse_args(argv)
    old = recorded()
    new = {"jax": jax.__version__, "texts": {}, "specs": {}}
    for family in old["texts"]:
        texts = lowered(pinned_engine(family), debug_info=False)
        new["texts"][family] = {k: text_pin(t) for k, t in texts.items()}
        new["specs"][family] = spec_pin(pinned_model(family))
        for kind in KINDS:
            was, now = old["texts"][family].get(kind), new["texts"][family][kind]
            if was != now:
                print(family, kind, was, "->", now)
        if old["specs"].get(family) != new["specs"][family]:
            print(family, "spec", old["specs"].get(family), "->", new["specs"][family])
    if old["jax"] != new["jax"]:
        print("jax", old["jax"], "->", new["jax"])
    if new == old:
        print("every pin holds")
    elif args.write:
        with open(PINS, "w") as f:
            f.write(dumped(new))
        print("written:", PINS)
    return 0 if new == old or args.write else 1


if __name__ == "__main__":
    raise SystemExit(main())
