"""Continuous-batching serving engine tests: greedy parity with
models.generate, mixed-length admission/retirement across steps WITHOUT
recompilation, and block-pool recycling."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def model():
    # a leaked fleet hybrid group (e.g. an earlier test file's mp>1 init)
    # would silently make this llama build TP-parallel layers and break
    # engine-vs-generate parity — build single-process explicitly
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    P.seed(11)
    # narrow config (ROADMAP item 6, tier-1 budget): these tests exercise
    # scheduling/admission/parity, none of which depends on width — but
    # KEEP 2 layers so the per-layer cache/scale threading stays covered
    from paddle_tpu.models.llama import LlamaConfig

    # (vocab stays 512: test prompts carry ids up to 410)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=2,
        max_position_embeddings=256))


def ref_greedy(model, prompt, n):
    from paddle_tpu.models.generation import generate

    ids = P.to_tensor(np.asarray(prompt, np.int32)[None, :])
    # the fixed-shape path (two programs): with growing caches every op of the
    # forward compiles again at every length, most of this reference's seconds
    out = generate(model, ids, max_new_tokens=n, do_sample=False,
                   use_static_cache=True)
    return list(np.asarray(out.numpy()).reshape(-1))


class TestBlockManagerGuards:
    """ISSUE 2 satellite: double-free silently corrupts allocation (two
    sequences handed the same block) — it must raise, naming the ids."""

    def test_double_free_raises_with_ids(self):
        from paddle_tpu.inference import BlockManager

        bm = BlockManager(8)
        blocks = bm.allocate(3)
        bm.free(blocks)
        with pytest.raises(RuntimeError, match="double-free"):
            bm.free([blocks[0]])
        # the error names the offending ids
        with pytest.raises(RuntimeError, match=str(blocks[1])):
            bm.free([blocks[1]])

    def test_repeated_ids_in_one_free_raise(self):
        from paddle_tpu.inference import BlockManager

        bm = BlockManager(8)
        a, b = bm.allocate(2)
        with pytest.raises(RuntimeError, match="repeated"):
            bm.free([a, a, b])
        # the failed free must not have mutated the free list
        assert bm.num_free == 6
        bm.free([a, b])
        assert bm.num_free == 8

    def test_out_of_range_ids_raise(self):
        from paddle_tpu.inference import BlockManager

        bm = BlockManager(4)
        with pytest.raises(RuntimeError, match="outside the pool"):
            bm.free([99])

    def test_allocate_returns_unique_ids(self):
        from paddle_tpu.inference import BlockManager

        bm = BlockManager(16)
        out = bm.allocate(16)
        assert len(set(out)) == 16
        bm.free(out)
        # interleaved alloc/free keeps ids unique
        x = bm.allocate(5)
        y = bm.allocate(5)
        assert not set(x) & set(y)


class TestServingEngine:
    def test_single_request_matches_generate(self, model):
        eng = ServingEngine(model, max_batch_size=2, max_seq_len=64,
                            block_size=8, token_budget=16)
        prompt = [3, 17, 101, 7, 250]
        rid = eng.add_request(prompt, max_new_tokens=8)
        out = eng.run()
        assert out[rid] == ref_greedy(model, prompt, 8)

    def test_mixed_lengths_no_recompile(self, model):
        """Admit sequences of different lengths at different times; the whole
        service runs from ONE compiled step program."""
        eng = ServingEngine(model, max_batch_size=3, max_seq_len=64,
                            block_size=8, token_budget=12)
        p1 = [3, 17, 101, 7, 250, 9, 12]
        p2 = [42, 5]
        p3 = [400, 401, 402, 403, 404, 405, 406, 407, 408, 409, 410]
        r1 = eng.add_request(p1, max_new_tokens=6)
        r2 = eng.add_request(p2, max_new_tokens=4)
        # a few steps in, admit a third request mid-flight
        eng.step()
        eng.step()
        r3 = eng.add_request(p3, max_new_tokens=5)
        out = eng.run()
        assert out[r1] == ref_greedy(model, p1, 6)
        assert out[r2] == ref_greedy(model, p2, 4)
        assert out[r3] == ref_greedy(model, p3, 5)
        if hasattr(eng._step_fn, "_cache_size"):
            # exactly two programs regardless of traffic: the mixed/prefill
            # step (mq=T) and the tight pure-decode step (mq=1)
            assert eng._step_fn._cache_size() <= 2

    def test_engines_share_compiled_programs(self, model):
        """Engines with identical trace-shaping config share one jitted
        program (and so its XLA compile cache): weights/caches/rope are
        call arguments, so nothing per-engine is baked into the trace.
        A different geometry (here token_budget) must NOT share."""
        kw = dict(max_batch_size=3, max_seq_len=64, block_size=8,
                  token_budget=12)
        e1 = ServingEngine(model, **kw)
        e2 = ServingEngine(model, **kw)
        assert e1._step_fn is e2._step_fn
        assert e1._forward is e2._forward
        e3 = ServingEngine(model, **{**kw, "token_budget": 16})
        assert e3._step_fn is not e1._step_fn
        # sharing must not change results: both engines serve correctly
        p = [3, 17, 101, 7]
        r1 = e1.add_request(p, max_new_tokens=5)
        r2 = e2.add_request(p, max_new_tokens=5)
        ref = ref_greedy(model, p, 5)
        assert e1.run()[r1] == ref
        assert e2.run()[r2] == ref

    def test_run_raises_on_max_steps_exhaustion(self, model):
        """ADVICE r5 low #1: a truncated run (max_steps hit with work still
        queued/active) must raise, not return a dict missing tokens."""
        eng = ServingEngine(model, max_batch_size=2, max_seq_len=64,
                            block_size=8, token_budget=16)
        eng.add_request([3, 17, 101], max_new_tokens=8)
        # one step = prefill + first token; the megastep would finish the
        # remaining 7 in step two, so step ONE is the truncation point
        with pytest.raises(RuntimeError, match="max_steps"):
            eng.run(max_steps=1)
        # draining the remaining steps finishes normally
        out = eng.run()
        assert len(next(iter(out.values()))) == 8

    def test_eviction_recycles_blocks_for_queued_requests(self, model):
        """More requests than slots/blocks: later requests wait, get admitted
        as earlier ones retire, and still decode correctly."""
        eng = ServingEngine(model, max_batch_size=2, max_seq_len=32,
                            block_size=8, token_budget=8,
                            num_blocks=8)  # tight pool: 2 seqs of 4 blocks
        prompts = [[3, 17, 101], [42, 5, 7, 9], [250, 4], [88, 13, 77]]
        rids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        assert eng.num_active <= 2
        out = eng.run()
        for rid, p in zip(rids, prompts):
            assert out[rid] == ref_greedy(model, p, 4)
        assert eng.blocks.num_free == 8  # everything returned to the pool

    def test_eos_early_retirement(self, model):
        prompt = [3, 17, 101, 7]
        full = ref_greedy(model, prompt, 8)
        eos = full[2]  # force early stop at the 3rd generated token
        eng = ServingEngine(model, max_batch_size=2, max_seq_len=64,
                            block_size=8, token_budget=16)
        rid = eng.add_request(prompt, max_new_tokens=8, eos_token_id=eos)
        out = eng.run()
        assert out[rid] == full[:3]

    def test_int8_paged_cache(self, model):
        """int8 cache-quant serving: uint8 paged
        blocks + per-(slot, kv-head) dynamic scales frozen at prefill;
        outputs stay token-identical to the fp engine on this model."""
        import jax.numpy as jnp

        eng = ServingEngine(model, max_batch_size=2, max_seq_len=64,
                            block_size=8, token_budget=16,
                            cache_quant="int8")
        assert eng.key_caches[0].dtype == jnp.uint8
        p1, p2 = [3, 17, 101, 7, 250], [42, 5, 9]
        r1 = eng.add_request(p1, max_new_tokens=6)
        r2 = eng.add_request(p2, max_new_tokens=6)
        out = eng.run()
        assert out[r1] == ref_greedy(model, p1, 6)
        assert out[r2] == ref_greedy(model, p2, 6)
        # prefill froze real scales for the active slots
        kd = np.asarray(eng.cache_scales[0]["kd"])
        assert (kd > 0).all()
        # the one-shot-prefill contract is enforced
        with pytest.raises(ValueError, match="one step"):
            eng.add_request(list(range(20)), max_new_tokens=2)

    def test_int8_prefill_never_chunked_under_load(self, model):
        """With decode traffic eating budget, an int8 prefill must WAIT for
        a one-shot slot rather than chunk (chunked prefills would freeze
        wrong dynamic scales) — and still decode correctly."""
        eng = ServingEngine(model, max_batch_size=2, max_seq_len=64,
                            block_size=8, token_budget=8,
                            cache_quant="int8")
        p1 = [3, 17, 101]
        r1 = eng.add_request(p1, max_new_tokens=10)
        eng.step()  # r1 prefills
        p2 = list(range(40, 48))  # exactly the budget: needs a full step
        r2 = eng.add_request(p2, max_new_tokens=4)
        out = eng.run()
        assert out[r1] == ref_greedy(model, p1, 10)
        assert out[r2] == ref_greedy(model, p2, 4)

    def test_chunked_prefill_long_prompt(self, model):
        """Prompt longer than the token budget: prefill spans several steps,
        output still matches."""
        eng = ServingEngine(model, max_batch_size=2, max_seq_len=64,
                            block_size=8, token_budget=8)
        prompt = list(range(30, 50))  # 20 tokens > budget 8
        rid = eng.add_request(prompt, max_new_tokens=5)
        out = eng.run()
        assert out[rid] == ref_greedy(model, prompt, 5)
