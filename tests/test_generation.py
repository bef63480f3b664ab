"""Autoregressive generation with KV caches (PaddleNLP generate-surface
capability; exercises the cache decode path + top_p_sampling)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaForCausalLM, generate, llama_tiny


def _model():
    P.seed(0)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


@pytest.mark.quick
def test_greedy_matches_full_forward():
    m = _model()
    ids = P.to_tensor(np.random.RandomState(0).randint(0, 512, (2, 8)).astype(np.int32))
    out = generate(m, ids, max_new_tokens=5)
    assert out.shape == [2, 5]
    # KV-cache decode must agree with re-running the full sequence
    full = np.concatenate([ids.numpy(), out.numpy()[:, :-1]], axis=1)
    logits = m(P.to_tensor(full.astype(np.int32)))
    ref_last = np.argmax(np.asarray(logits._value[:, -1, :], np.float32), axis=-1)
    np.testing.assert_array_equal(out.numpy()[:, -1], ref_last)


def test_sampling_and_eos():
    m = _model()
    ids = P.to_tensor(np.random.RandomState(1).randint(0, 512, (1, 4)).astype(np.int32))
    P.seed(7)
    out1 = generate(m, ids, max_new_tokens=4, do_sample=True, top_p=0.9)
    assert out1.shape[1] <= 4
    # eos early stop: force eos to the greedy first token -> stops after 1
    first = int(generate(m, ids, max_new_tokens=1).numpy()[0, 0])
    out2 = generate(m, ids, max_new_tokens=6, eos_token_id=first)
    assert out2.shape[1] == 1


def test_zero_budget_returns_empty():
    m = _model()
    ids = P.to_tensor(np.random.RandomState(2).randint(0, 512, (2, 4)).astype(np.int32))
    out = generate(m, ids, max_new_tokens=0)
    assert out.shape == [2, 0]


@pytest.mark.parametrize("batch,prompt_len,new", [
    (2, 6, 6),
    (1, 2, 6), (1, 3, 30), (1, 5, 12), (1, 8, 48), (1, 11, 4), (1, 24, 6), (1, 40, 6),
    (1, 250, 6)])
def test_static_cache_matches_dynamic(serving_model, batch, prompt_len, new):
    """Fixed-size KV ring decode == growing-cache decode, with exactly TWO
    compiled programs (prefill + decode) regardless of sequence length.

    The serving test files' ``ref_greedy`` asks ``use_static_cache=True`` (the
    growing caches compile every op again at every length), so the plain
    growing-cache forward, which ``TestGrowingCacheStep`` holds to the full
    forward, guards that reference here: ``llama_tiny`` at a batch of two, and
    those files' own model with one prompt as they send it, at their prompt
    lengths and new-token counts (2-40 tokens, 2-48 new) and up to the last
    rope row (250 + 6 = ``max_position_embeddings``)."""
    m = _model() if batch == 2 else serving_model
    ids = P.to_tensor(np.random.RandomState(3).randint(
        0, m.config.vocab_size, (batch, prompt_len)).astype(np.int32))
    ref = generate(m, ids, max_new_tokens=new)
    out = generate(m, ids, max_new_tokens=new, use_static_cache=True)
    np.testing.assert_array_equal(out.numpy(), ref.numpy())


def test_static_cache_compile_count():
    from paddle_tpu.jit.api import StaticFunction

    m = _model()
    st = StaticFunction(m)
    B, S, L = 1, 4, 12
    cfg = m.config
    import jax.numpy as jnp

    from paddle_tpu.tensor.tensor import Tensor

    caches = [(Tensor(jnp.zeros((B, L, cfg.num_key_value_heads, cfg.head_dim))),
               Tensor(jnp.zeros((B, L, cfg.num_key_value_heads, cfg.head_dim))),
               Tensor(jnp.zeros((), jnp.int32)))
              for _ in range(cfg.num_hidden_layers)]
    ids = P.to_tensor(np.random.RandomState(0).randint(0, 512, (B, S)).astype(np.int32))
    logits, caches = st(ids, caches=caches)
    n_prefill = len(st._cache)
    for _ in range(5):
        tok = P.to_tensor(np.array([[7]], np.int32))
        logits, caches = st(tok, caches=caches)
    assert n_prefill == 1
    assert len(st._cache) == 2  # prefill + ONE decode program for all steps


def test_greedy_decode_compiled_loop_matches():
    from paddle_tpu.models import greedy_decode

    m = _model()
    ids = P.to_tensor(np.random.RandomState(5).randint(0, 512, (2, 6)).astype(np.int32))
    ref = generate(m, ids, max_new_tokens=6)
    out = greedy_decode(m, ids, max_new_tokens=6)
    np.testing.assert_array_equal(out.numpy(), ref.numpy())
    # second call reuses the compiled program (guard-cache hit)
    out2 = greedy_decode(m, ids, max_new_tokens=6)
    np.testing.assert_array_equal(out2.numpy(), ref.numpy())
    st = m._decode_cache[next(iter(m._decode_cache))]
    assert len(st._cache) == 1


def test_static_cache_guards():
    import pytest as _pt

    from paddle_tpu.models import GPTForCausalLM, greedy_decode, gpt_tiny

    m = _model()
    ids = P.to_tensor(np.random.RandomState(6).randint(0, 512, (1, 4)).astype(np.int32))
    with _pt.raises(ValueError, match="KV ring"):
        generate(m, ids, max_new_tokens=8, use_static_cache=True, max_length=6)
    with _pt.raises(ValueError, match="KV ring"):
        greedy_decode(m, ids, max_new_tokens=8, max_length=6)
    assert greedy_decode(m, ids, max_new_tokens=0).shape == [1, 0]
    gm = GPTForCausalLM(gpt_tiny())
    gm.eval()
    with _pt.raises(ValueError, match="static KV"):
        generate(gm, ids, max_new_tokens=4, use_static_cache=True)


def test_static_cache_rejects_beyond_rope_table():
    import pytest as _pt

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, greedy_decode

    P.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      max_position_embeddings=8)
    m = LlamaForCausalLM(cfg)
    m.eval()
    ids = P.to_tensor(np.random.RandomState(0).randint(0, 64, (1, 6)).astype(np.int32))
    with _pt.raises(ValueError, match="max_position_embeddings"):
        greedy_decode(m, ids, max_new_tokens=6)
    with _pt.raises(ValueError, match="max_position_embeddings"):
        generate(m, ids, max_new_tokens=6, use_static_cache=True)


class TestGrowingCacheStep:
    """``forward(ids, caches=[(k, v), ...])`` is the step ``generate`` runs with
    growing caches: its logits are the full forward's at the same positions,
    whatever the cached length (255 is ``llama_tiny``'s last rope row).  The
    serving tests hold the engine to ``generate``'s fixed-shape path, and
    ``test_static_cache_matches_dynamic`` holds that path to this one."""

    @staticmethod
    def _model(**kw):
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        P.seed(3)
        model = LlamaForCausalLM(llama_tiny(**kw))
        model.eval()
        return model

    @staticmethod
    def _empty(model, batch):
        import jax.numpy as jnp

        cfg = model.config
        shape = (batch, 0, cfg.num_key_value_heads, cfg.head_dim)
        return [(P.to_tensor(jnp.zeros(shape, jnp.float32)),
                 P.to_tensor(jnp.zeros(shape, jnp.float32)))
                for _ in range(cfg.num_hidden_layers)]

    def _check(self, model, cached, new, fwd=None):
        fwd = fwd or model
        ids = np.random.RandomState(cached).randint(
            0, model.config.vocab_size, (2, cached + new)).astype(np.int32)
        with P.no_grad():
            full = model(P.to_tensor(ids)).numpy()
            caches = self._empty(model, 2)
            if cached:
                prefill, caches = fwd(P.to_tensor(ids[:, :cached]), caches=caches)
                np.testing.assert_allclose(prefill.numpy(), full[:, :cached], atol=2e-4)
            step, caches = fwd(P.to_tensor(ids[:, cached:]), caches=caches)
        np.testing.assert_allclose(step.numpy(), full[:, cached:], atol=2e-4)
        assert [tuple(c.shape) for c in caches[0]] == [
            (2, cached + new, model.config.num_key_value_heads, model.config.head_dim)] * 2

    @pytest.mark.parametrize("cached", [0, 5, 130, 255])
    def test_one_token_step_matches_full_forward(self, cached):
        self._check(self._model(), cached, 1)

    def test_grouped_kv_heads_and_a_step_of_three(self):
        self._check(self._model(num_key_value_heads=2), 5, 3)

    def test_under_to_static(self):
        from paddle_tpu.jit import to_static

        model = self._model()
        self._check(model, 5, 1, fwd=to_static(model))
