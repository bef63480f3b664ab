"""Megastep decode + in-graph sampling + token streaming (ISSUE 9),
mixed-phase chunked prefill + in-graph deadlines + int8 scan carry
(ISSUE 16).

Contracts under test:

* K>1 megastep decode is token-identical to K=1 per-token stepping and
  to the engine-independent greedy reference (``models.generate``) —
  with the prefix cache on AND off, and across recompute preemption
  (evict at a megastep boundary, resume with prompt+generated);
* MIXED-PHASE (ISSUE 16): under staggered open-loop admission the scan
  packs one prompt chunk per prefilling row alongside the decode rows —
  token-identical to per-token stepping (greedy AND seeded), with the
  prefix cache entering prefill mid-chunk, across a preempt/resume that
  straddles a chunk boundary, and with ``prefill_chunk`` span events
  attributing TTFT chunk by chunk; a mixed launch divides its token
  budget by chunk (PR 25), so every waiting prompt that fits prefills
  in the same launch, in admission order, and none is starved;
* ``temperature=0`` sampling is the argmax path exactly (same tokens as
  the greedy engine), and seeded sampling is deterministic: same seed →
  same tokens across K values, across an engine rebuild (the worker-
  restart shape), and across a preempt/resume with ``sample_offset``;
* streaming surfaces every token exactly once, in order, both through
  ``on_token`` callbacks and the ``stream()`` iterator;
* deadline budgets ride the scan carry as data (ISSUE 16): a row
  freezes in-graph AT its deadline — zero token overshoot when the
  engine has a per-iteration time estimate, K-bounded before the first
  measurement (the superseded ISSUE 9 contract, kept as the fallback);
* ``cache_quant='int8'`` decodes through the scan (scales in the
  carry) with token parity vs the per-token int8 path;
* logprobs align 1:1 with tokens and survive the result plumbing.
"""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference import (
    FlightRecorder,
    Priority,
    RequestStatus,
    SamplingParams,
    ServingEngine,
    ServingFrontend,
    TraceContext,
    Tracer,
)
from paddle_tpu.inference.launch_block import ResultBlock
from paddle_tpu.inference.tracing import tree_complete

pytestmark = pytest.mark.quick

ENGINE = dict(max_batch_size=2, max_seq_len=64, block_size=8,
              token_budget=16)
SAMPLED = dict(temperature=0.8, top_k=50, top_p=0.95, seed=13)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def model(serving_model):
    # shared session-scoped sub-tiny model (tests/conftest.py, ROADMAP
    # item 6); topology reset stays per-module for leaked fleet groups
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    return serving_model


def ref_greedy(model, prompt, n):
    from paddle_tpu.models.generation import generate

    ids = P.to_tensor(np.asarray(prompt, np.int32)[None, :])
    # the fixed-shape path (two programs): with growing caches every op of the
    # forward compiles again at every length, most of this reference's seconds
    out = generate(model, ids, max_new_tokens=n, do_sample=False,
                   use_static_cache=True)
    return list(np.asarray(out.numpy()).reshape(-1))


def run_engine(model, prompt, n, k, sampling=None, **kw):
    eng = ServingEngine(model, megastep_k=k, **{**ENGINE, **kw})
    rid = eng.add_request(prompt, max_new_tokens=n, sampling=sampling)
    return eng.run()[rid], eng


class TestTokenIdentity:
    def test_k_gt_1_identical_to_k1_and_reference(self, model):
        """The headline contract: megastep partitioning of decode never
        changes greedy output — K=1, K=2, K=8 and the pre-megastep
        per-step reference all agree."""
        prompt = [3, 17, 101, 7, 250]
        ref = ref_greedy(model, prompt, 12)
        for k in (1, 2, 8):
            out, eng = run_engine(model, prompt, 12, k)
            assert out == ref, f"megastep_k={k} diverged"
            if k > 1:
                assert eng.megasteps > 0          # the scan path actually ran
                assert eng.megastep_tokens > 0

    def test_identical_with_prefix_cache_on_and_off(self, model):
        """Cache-on and cache-off megastep runs are token-identical (the
        shared-prefix second request prefill-skips into a megastep)."""
        shared = list(range(30, 46))              # 2 full blocks
        prompts = [shared + [7, 9], shared + [5]]
        outs = {}
        for cache in (False, "auto"):
            eng = ServingEngine(model, prefix_cache=cache, megastep_k=8,
                                **ENGINE)
            r0 = eng.add_request(prompts[0], max_new_tokens=8)
            first = eng.run()[r0]
            r1 = eng.add_request(prompts[1], max_new_tokens=8)
            outs[cache] = (first, eng.run()[r1])
            if cache == "auto":
                assert eng.prefix_hit_blocks > 0  # the cache really engaged
        assert outs[False] == outs["auto"]

    def test_preempt_resume_across_megastep_boundary(self, model):
        """Evict at a megastep boundary mid-generation, resume with
        prompt+generated: the concatenated stream equals the unpreempted
        run (greedy-deterministic contract carried through megastep)."""
        prompt = [3, 17, 101]
        full = ref_greedy(model, prompt, 12)
        eng = ServingEngine(model, megastep_k=4, **ENGINE)
        rid = eng.add_request(prompt, max_new_tokens=12)
        eng.step()       # prefill + first token
        eng.step()       # one K=4 megastep -> 5 tokens
        req = eng.evict(rid)
        assert 0 < len(req.generated) < 12
        rid2 = eng.add_request(prompt + req.generated,
                               max_new_tokens=12 - len(req.generated))
        out = eng.run()[rid2]
        assert req.generated + out == full


class TestSamplingDeterminism:
    def test_temperature_zero_is_argmax(self, model):
        """temperature=0 sampling takes the exact greedy path."""
        prompt = [3, 17, 101, 7]
        ref = ref_greedy(model, prompt, 10)
        out, _ = run_engine(model, prompt, 10, 8,
                            sampling={"temperature": 0.0, "seed": 99})
        assert out == ref

    def test_same_seed_same_tokens_across_k(self, model):
        """The key depends only on (seed, sample index): K=1 and K=8
        produce the same sampled stream; a different seed diverges."""
        prompt = [3, 17, 101, 7]
        out1, _ = run_engine(model, prompt, 10, 1, sampling=SAMPLED)
        out8, _ = run_engine(model, prompt, 10, 8, sampling=SAMPLED)
        assert out1 == out8
        other, _ = run_engine(model, prompt, 10, 8,
                              sampling={**SAMPLED, "seed": 14})
        assert other != out8

    def test_replay_across_engine_rebuild(self, model):
        """The worker-restart shape: a fresh engine (rebuilt caches and
        programs, same seeded model) replays the same sampled stream."""
        prompt = [42, 5, 7]
        first, eng = run_engine(model, prompt, 8, 8, sampling=SAMPLED)
        del eng
        again, _ = run_engine(model, prompt, 8, 8, sampling=SAMPLED)
        assert first == again

    def test_resume_continues_key_stream_via_sample_offset(self, model):
        """A preempted sampled request resumed with ``sample_offset``
        continues the seeded stream exactly where it stopped."""
        prompt = [3, 17, 101]
        full, _ = run_engine(model, prompt, 12, 8, sampling=SAMPLED)
        eng = ServingEngine(model, megastep_k=4, **ENGINE)
        rid = eng.add_request(prompt, max_new_tokens=12, sampling=SAMPLED)
        eng.step()
        eng.step()
        req = eng.evict(rid)
        assert 0 < len(req.generated) < 12
        assert full[:len(req.generated)] == req.generated
        rid2 = eng.add_request(prompt + req.generated,
                               max_new_tokens=12 - len(req.generated),
                               sampling=SAMPLED,
                               sample_offset=len(req.generated))
        out = eng.run()[rid2]
        assert req.generated + out == full

    def test_frontend_preemption_preserves_sampled_stream(self, model):
        """End to end through the control plane: a LOW sampled request
        preempted for a HIGH one resumes (the frontend passes
        sample_offset) and finishes with the unpreempted stream."""
        plo = [3, 17, 101]
        want, _ = run_engine(model, plo, 8, 8, sampling=SAMPLED,
                             max_seq_len=32, num_blocks=4)
        eng = ServingEngine(model, megastep_k=8, **{**ENGINE,
                                                    "max_seq_len": 32,
                                                    "num_blocks": 4})
        fe = ServingFrontend([eng])
        rlo = fe.submit(plo, max_new_tokens=8, priority=Priority.LOW,
                        **SAMPLED)
        fe.step()                                # prefill + first token
        rhi = fe.submit(list(range(40, 50)), max_new_tokens=8,
                        priority=Priority.HIGH)
        res = fe.run()
        assert res[rhi].ok
        assert res[rlo].ok and res[rlo].preemptions >= 1
        assert res[rlo].tokens == want

    def test_sampling_validation(self, model):
        with pytest.raises(ValueError, match="temperature"):
            SamplingParams(temperature=-0.1)
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=0.0)
        with pytest.raises(ValueError, match="top_k"):
            SamplingParams(top_k=-1)


class TestSampleProbs:
    """capture_sample_probs=True (ISSUE 11 satellite): the engine
    exposes the renormalized POST-top-k/top-p distribution each token
    was drawn from — the q(x) a speculative-decode verifier scores draft
    tokens against — harvested like pop_token_logprobs()."""

    def test_probs_align_and_respect_filters(self, model):
        eng = ServingEngine(model, megastep_k=4,
                            capture_sample_probs=True, **ENGINE)
        rs = eng.add_request([3, 17, 101, 7], max_new_tokens=6,
                             sampling={"temperature": 0.8, "top_k": 8,
                                       "top_p": 0.9, "seed": 13})
        rg = eng.add_request([42, 5], max_new_tokens=6)     # greedy
        toks = eng.run()
        probs = eng.pop_sample_probs()
        assert set(probs) == {rs, rg}
        for rid in (rs, rg):
            assert len(probs[rid]) == len(toks[rid])   # 1:1 with tokens
            for q, t in zip(probs[rid], toks[rid]):
                assert float(q.sum()) == pytest.approx(1.0, abs=1e-4)
                assert q[t] > 0          # drawn token is inside support
        for q in probs[rs]:
            assert int((q > 0).sum()) <= 8        # top-k support bound
        for q, t in zip(probs[rg], toks[rg]):
            assert q[t] == 1.0 and int((q > 0).sum()) == 1   # one-hot
        assert eng.pop_sample_probs() == {}       # drained

    def test_capture_does_not_change_tokens(self, model):
        """Bit-identical draws with the capture on and off, single-step
        (K=1) and megastep (K=8) paths both."""
        prompt = [3, 17, 101, 7]
        for k in (1, 8):
            off, _ = run_engine(model, prompt, 8, k, sampling=SAMPLED)
            on, _ = run_engine(model, prompt, 8, k, sampling=SAMPLED,
                               capture_sample_probs=True)
            assert on == off, f"K={k}"
            goff, _ = run_engine(model, prompt, 8, k)
            gon, _ = run_engine(model, prompt, 8, k,
                                capture_sample_probs=True)
            assert gon == goff, f"K={k} greedy"

    def test_probs_are_the_sampled_distribution(self, model):
        """The spec-decode verification property: redrawing under the
        request's own (seed, sample-index) key from the EXPOSED
        distribution reproduces the engine's token exactly (categorical
        is shift-invariant, so log q and the filtered logits draw the
        same sample)."""
        import jax
        import jax.numpy as jnp

        sp = {"temperature": 0.8, "top_k": 16, "top_p": 0.95, "seed": 21}
        eng = ServingEngine(model, megastep_k=4,
                            capture_sample_probs=True, **ENGINE)
        rid = eng.add_request([9, 2, 77], max_new_tokens=6, sampling=sp)
        toks = eng.run()[rid]
        qs = eng.pop_sample_probs()[rid]
        for i, (q, t) in enumerate(zip(qs, toks)):
            key = jax.random.fold_in(jax.random.PRNGKey(sp["seed"]), i)
            redraw = int(jax.random.categorical(
                key, jnp.log(jnp.asarray(q))))
            assert redraw == t, f"sample index {i}"


class TestStreaming:
    def test_on_token_callback_order_and_completeness(self, model):
        fe = ServingFrontend([ServingEngine(model, **ENGINE)])
        seen = {}
        rids = [fe.submit([3 + i, 17, 101], max_new_tokens=10,
                          on_token=lambda rid, t: seen.setdefault(
                              rid, []).append(t))
                for i in range(3)]
        res = fe.run()
        for rid in rids:
            assert res[rid].ok
            assert seen[rid] == res[rid].tokens   # every token, in order

    def test_stream_iterator(self, model):
        fe = ServingFrontend([ServingEngine(model, **ENGINE)])
        rid = fe.submit([3, 17, 101], max_new_tokens=10)
        toks = list(fe.stream(rid))
        assert toks == fe.result(rid).tokens
        assert fe.result(rid).ok
        with pytest.raises(KeyError):
            next(fe.stream(999))

    def test_raising_callback_disables_stream_not_replica(self, model):
        """A buggy on_token callback must not kill the replica or the
        request — the callback is dropped, the request completes."""
        def boom(rid, tok):
            raise RuntimeError("consumer bug")

        fe = ServingFrontend([ServingEngine(model, **ENGINE)])
        rid = fe.submit([3, 17, 101], max_new_tokens=8, on_token=boom)
        res = fe.run()
        assert res[rid].ok
        assert res[rid].tokens == ref_greedy(model, [3, 17, 101], 8)
        assert fe.metrics.counter("stream_callback_errors_total") == 1
        assert fe.metrics.counter("replica_deaths_total") == 0


class TestMegastepBoundaries:
    def test_deadline_shed_zero_overshoot_in_graph(self, model):
        """ISSUE 16 (supersedes test_deadline_overshoot_bounded_by_k):
        the deadline rides the scan carry as a per-row iteration budget
        decremented in-graph, so the row freezes AT its deadline — zero
        token overshoot — and the frontend's next boundary check turns
        the frozen row into the typed shed.  ``deadline_token_seconds``
        injects the per-iteration estimate so the budget is exact."""
        clock = FakeClock()
        eng = ServingEngine(model, megastep_k=4,
                            deadline_token_seconds=1.0, clock=clock,
                            **ENGINE)
        fe = ServingFrontend([eng], clock=clock)
        rid = fe.submit([3, 17, 101], max_new_tokens=30, deadline_s=100.0)
        fe.step()                 # prefill + first token at t=0
        clock.t = 97.0            # 3 iteration budgets remain
        fe.step()                 # K=4 scan with in-graph budget dl=3
        assert fe.result(rid) is None      # frozen, not yet expired
        clock.t = 101.0
        fe.step()                 # boundary: typed shed of the frozen row
        res = fe.result(rid)
        assert res is not None
        assert res.status is RequestStatus.DEADLINE_EXCEEDED
        # 1 prefill-step token + the in-graph budget of exactly 3: the
        # K=4 scan stopped one short of its sweep — ZERO overshoot
        assert len(res.tokens) == 4
        assert res.tokens == ref_greedy(model, [3, 17, 101], 30)[:4]
        assert eng.megasteps > 0           # the scan path really ran

    def test_deadline_fallback_bounded_by_k_without_estimate(self, model):
        """Before the engine has measured a megastep (no injected
        ``deadline_token_seconds``, first launch is a compile), the
        in-graph budget is unarmed and the ISSUE 9 bound is the worst
        case: at most K extra tokens from the straddling megastep."""
        clock = FakeClock()
        eng = ServingEngine(model, megastep_k=4, **ENGINE)
        fe = ServingFrontend([eng], clock=clock)
        rid = fe.submit([3, 17, 101], max_new_tokens=30, deadline_s=5.0)
        fe.step()                     # prefill + first token
        clock.advance(10.0)           # deadline passes between boundaries
        fe.step()                     # boundary: shed fires HERE
        res = fe.result(rid)
        assert res is not None
        assert res.status is RequestStatus.DEADLINE_EXCEEDED
        # 1 pre-deadline token; the straddling megastep can add at most K
        assert len(res.tokens) <= 1 + eng.megastep_k
        assert res.tokens == ref_greedy(model, [3, 17, 101],
                                        30)[:len(res.tokens)]

    def test_logprobs_align_with_tokens(self, model):
        fe = ServingFrontend([ServingEngine(model, **ENGINE)])
        r1 = fe.submit([3, 17, 101], max_new_tokens=9, logprobs=True)
        r2 = fe.submit([42, 5], max_new_tokens=6, logprobs=True,
                       **SAMPLED)
        res = fe.run()
        for rid in (r1, r2):
            lps = res[rid].logprobs
            assert lps is not None and len(lps) == len(res[rid].tokens)
            assert all(lp <= 0.0 for lp in lps)   # log-probabilities
        # greedy default requests don't pay for logprob plumbing
        r3 = fe.submit([9, 9], max_new_tokens=4)
        assert fe.run()[r3].logprobs is None

    def test_megastep_counters_and_state_summary(self, model):
        eng = ServingEngine(model, megastep_k=8, **ENGINE)
        fe = ServingFrontend([eng])
        rid = fe.submit([3, 17, 101], max_new_tokens=10)
        res = fe.run()
        assert res[rid].ok
        ms = eng.state_summary()["megastep"]
        assert ms["k"] == 8
        assert ms["megasteps"] == eng.megasteps > 0
        assert ms["tokens"] == eng.megastep_tokens > 0
        assert fe.metrics.counter("megasteps_total") == eng.megasteps
        assert (fe.metrics.counter("megastep_tokens_total")
                == eng.megastep_tokens)

    def test_megastep_k1_never_scans(self, model):
        out_ref = ref_greedy(model, [3, 17, 101], 8)
        eng = ServingEngine(model, megastep_k=1, **ENGINE)
        rid = eng.add_request([3, 17, 101], max_new_tokens=8)
        assert eng.run()[rid] == out_ref
        # never armed: zero scan launches (the program object itself may
        # be pre-warmed from the process-wide shared program cache)
        assert eng.megasteps == 0

    def test_megastep_k_validation(self, model):
        with pytest.raises(ValueError, match="megastep_k"):
            ServingEngine(model, megastep_k=0, **ENGINE)


def run_staggered(model, prompts, arrivals, k, n=8, sampling=None, **kw):
    """Open-loop staggered admission in engine-step time: request i is
    admitted once the step counter reaches ``arrivals[i]`` — the traffic
    shape where the r11 arming rule (megastep only when EVERY scheduled
    row is past prefill) degraded to per-token stepping."""
    eng = ServingEngine(model, megastep_k=k, **{**ENGINE, **kw})
    out, rids, nxt, steps = {}, [], 0, 0
    while True:
        while nxt < len(prompts) and arrivals[nxt] <= steps:
            rid = eng.add_request(prompts[nxt], max_new_tokens=n,
                                  sampling=sampling)
            rids.append(rid)
            out[rid] = []
            nxt += 1
        st = eng.state_summary()
        if st["num_active"] == 0 and st["queue_depth"] == 0:
            if nxt >= len(prompts):
                break
            steps = arrivals[nxt]     # idle gap: jump to the next arrival
            continue
        for rid, toks in eng.step().items():
            out[rid].extend(toks)
        steps += 1
    return [out[r] for r in rids], eng


class TestMixedPhaseMegastep:
    """ISSUE 16: chunked prefill INSIDE the scan.  Each iteration
    processes, per row, one decode token or one ≤block_size prompt
    chunk (fed as data through ``prefill_pos`` carries), so the
    megastep arms whenever any row is decoding and never disarms under
    open-loop admission."""

    PROMPTS = ([3, 17, 101],
               [40, 41, 42, 43, 44, 45, 46, 47, 48, 49],
               [7, 9],
               [90, 91, 92, 93, 94])
    ARRIVALS = (0, 1, 3, 5)

    def test_staggered_greedy_parity_and_stays_armed(self, model):
        """The headline contract both ways: chunked-on/off token
        identity under staggered admission, and the scan actually
        stayed armed (mixed launches + chunks fed happened)."""
        on, eng = run_staggered(model, self.PROMPTS, self.ARRIVALS, 4)
        off, _ = run_staggered(model, self.PROMPTS, self.ARRIVALS, 1)
        assert on == off
        for p, toks in zip(self.PROMPTS, on):
            assert toks == ref_greedy(model, p, 8)
        assert eng.megasteps_mixed > 0        # prefill rode the scan
        assert eng.prefill_chunks > 0
        ms = eng.state_summary()["megastep"]
        assert ms["mixed"] == eng.megasteps_mixed
        assert ms["prefill_chunks"] == eng.prefill_chunks

    def test_staggered_seeded_parity(self, model):
        """Seeded sampling through the mixed scan: the (seed, sample
        index) key contract is phase-blind, so chunked-on/off streams
        are identical."""
        on, eng = run_staggered(model, self.PROMPTS, self.ARRIVALS, 4,
                                sampling=SAMPLED)
        off, _ = run_staggered(model, self.PROMPTS, self.ARRIVALS, 1,
                               sampling=SAMPLED)
        assert on == off
        assert eng.megasteps_mixed > 0

    def test_prefix_hit_enters_mid_chunk(self, model):
        """A prefix-cache hit drops a prompt into prefill at its first
        uncached position — mid-chunk from the scan's point of view (the
        chunk window starts at ``prefill_pos``, not a chunk-0 boundary).
        Cache-on and cache-off runs stay token-identical."""
        shared = list(range(30, 46))          # 16 tokens = 2 full blocks
        outs = {}
        for cache in (False, "auto"):
            eng = ServingEngine(model, prefix_cache=cache, megastep_k=4,
                                **ENGINE)
            r0 = eng.add_request(shared + [7, 9], max_new_tokens=8)
            first = eng.run()[r0]             # seeds the cache
            rd = eng.add_request([3, 17, 101], max_new_tokens=10)
            eng.step()                        # rd past prefill: decoding
            r1 = eng.add_request(shared + [5], max_new_tokens=8)
            rest = eng.run()
            outs[cache] = (first, rest[rd], rest[r1])
            if cache == "auto":
                assert eng.prefix_hit_blocks > 0   # the cache engaged
                assert eng.megasteps_mixed > 0     # hit rode the scan
        assert outs[False] == outs["auto"]

    def test_preempt_resume_across_chunk_boundary(self, model):
        """Evict a request mid-prefill — after the mixed scan fed some
        chunks but before the prompt completed — and resume it: the
        re-queued run and the concurrent decode row both match the
        unpreempted greedy reference."""
        long = list(range(40, 64))            # 24 tokens = 3 chunks of 8
        eng = ServingEngine(model, megastep_k=2, **ENGINE)
        r0 = eng.add_request([3, 17, 101], max_new_tokens=12)
        eng.step()                            # prefill + first token
        r1 = eng.add_request(long, max_new_tokens=6)
        eng.step()                # mixed K=2 scan: 2 chunks of r1 fed
        req = eng._active[r1]
        assert 0 < req.prefill_pos < len(long)    # mid-prefill
        assert req.chunks_fed >= 1            # crossed a chunk boundary
        evicted = eng.evict(r1)
        assert evicted.generated == []        # preempted before token 1
        r2 = eng.add_request(long, max_new_tokens=6)
        out = eng.run()
        assert out[r2] == ref_greedy(model, long, 6)
        assert out[r0] == ref_greedy(model, [3, 17, 101], 12)

    def test_int8_scan_carry_parity(self, model):
        """cache_quant='int8' rides the pure-decode scan (the quant
        scales travel in the carry): K>1 matches the per-token int8
        path exactly, greedy and seeded."""
        prompt = [3, 17, 101, 7]
        off, eoff = run_engine(model, prompt, 10, 1, cache_quant="int8")
        on, eon = run_engine(model, prompt, 10, 4, cache_quant="int8")
        assert on == off
        assert eon.megasteps > 0 and eoff.megasteps == 0
        s_off, _ = run_engine(model, prompt, 10, 1, cache_quant="int8",
                              sampling=SAMPLED)
        s_on, _ = run_engine(model, prompt, 10, 4, cache_quant="int8",
                             sampling=SAMPLED)
        assert s_on == s_off

    def test_int8_staggered_excludes_mixed_but_scans_decode(self, model):
        """int8's one-shot prefill contract (scales freeze at the full
        prompt) keeps prefill OUT of the mixed scan — chunk feeds would
        re-freeze scales per chunk — but decode still megasteps, and
        parity holds under staggered admission."""
        on, eng = run_staggered(model, self.PROMPTS, self.ARRIVALS, 4,
                                cache_quant="int8")
        off, _ = run_staggered(model, self.PROMPTS, self.ARRIVALS, 1,
                               cache_quant="int8")
        assert on == off
        assert eng.megasteps_mixed == 0       # contract: no int8 chunks
        assert eng.megasteps > 0              # decode rode the scan

    def test_mixed_counters_fold_through_frontend(self, model):
        eng = ServingEngine(model, megastep_k=4, **ENGINE)
        fe = ServingFrontend([eng])
        r0 = fe.submit([3, 17, 101], max_new_tokens=10)
        fe.step()                             # r0 decoding
        r1 = fe.submit(list(range(40, 50)), max_new_tokens=8)
        res = fe.run()
        assert res[r0].ok and res[r1].ok
        assert eng.megasteps_mixed > 0
        assert (fe.metrics.counter("megastep_mixed_total")
                == eng.megasteps_mixed)
        assert (fe.metrics.counter("prefill_chunks_total")
                == eng.prefill_chunks > 0)

    def test_prefill_chunk_trace_events(self, model):
        """r15 span events at chunk boundaries: every chunk feed lands
        a ``prefill_chunk`` event (index + token count) on the request's
        attempt span, so TTFT attributes across chunks fleet-wide."""
        clock = FakeClock()
        tracer = Tracer(clock=clock, proc="frontend")
        rec = FlightRecorder(clock=clock, proc="engine")
        eng = ServingEngine(model, megastep_k=2, trace_recorder=rec,
                            clock=clock, **ENGINE)
        fe = ServingFrontend([eng], tracer=tracer, clock=clock)
        r0 = fe.submit([3, 17, 101], max_new_tokens=8)
        fe.step()
        long = list(range(40, 60))            # 20 tokens: chunks 8, 8, 4
        r1 = fe.submit(long, max_new_tokens=4)
        res = fe.run()
        assert res[r0].ok and res[r1].ok
        tree = tracer.tree_for(TraceContext.mint(r1).trace_id)
        ok, why = tree_complete(tree)
        assert ok, why
        chunks = [e["attrs"] for evs in tree.values() for e in evs
                  if e["event"] == "prefill_chunk"]
        assert len(chunks) >= 2               # fed across scan launches
        assert sorted(a["chunk"] for a in chunks) == \
            list(range(len(chunks)))
        assert sum(a["tokens"] for a in chunks) == len(long)

    # ---- PR 25: a mixed launch divides its token budget by chunk ----
    # one row decodes, three prompts of several chunks wait: four slots,
    # room in the [T] buffer for the decode token and three chunks of 8
    SHARED = dict(max_batch_size=4, token_budget=32)
    WAITING = (list(range(40, 60)),           # 20 tokens: chunks 8, 8, 4
               list(range(70, 94)),           # 24 tokens: 8, 8, 8
               list(range(110, 128)))         # 18 tokens: 8, 8, 2

    def _decoding_then_waiting(self, model, k, waiting=None, sampling=None,
                               **kw):
        """An engine with one row past its prefill and ``waiting``
        admitted behind it: the state the next ``step()`` routes."""
        eng = ServingEngine(model, megastep_k=k,
                            **{**ENGINE, **self.SHARED, **kw})
        r0 = eng.add_request([3, 17, 101], max_new_tokens=12,
                             sampling=sampling)
        eng.step()                            # r0 decoding
        rids = [eng.add_request(p, max_new_tokens=6, sampling=sampling)
                for p in (self.WAITING if waiting is None else waiting)]
        return eng, r0, rids

    @pytest.mark.parametrize("sampling", [None, SAMPLED],
                             ids=["greedy", "seeded"])
    def test_one_launch_advances_every_waiting_prompt(self, model, sampling):
        """ONE mixed launch feeds all three waiting prompts a chunk an
        iteration (the old booking gave the whole budget to the first),
        and the tokens are those of per-token stepping and, greedy, of
        the model's own forward."""
        eng, r0, rids = self._decoding_then_waiting(model, 2,
                                                    sampling=sampling)
        before = eng.prefill_chunks
        eng.step()                            # K=2: two chunks a row
        assert eng.megasteps_mixed == 1
        assert [eng._active[r].prefill_pos for r in rids] == [16, 16, 16]
        assert eng.prefill_chunks - before == 6
        on = eng.run()
        ref, r0k1, rk1 = self._decoding_then_waiting(model, 1,
                                                     sampling=sampling)
        off = ref.run()
        assert [on[r0]] + [on[r] for r in rids] == \
            [off[r0k1]] + [off[r] for r in rk1]
        if sampling is None:
            assert on[r0] == ref_greedy(model, [3, 17, 101], 12)
            for r, p in zip(rids, self.WAITING):
                assert on[r] == ref_greedy(model, p, 6)

    def test_a_prompt_that_does_not_fit_waits_its_turn(self, model):
        """Room for two chunks beside the decode row: the third prompt
        waits, nobody behind it overtakes it (a 2-token prompt would
        fit the 3 tokens left), and both enter, in admission order, as
        soon as rows ahead of them finish their prompts."""
        waiting = self.WAITING + ([7, 9],)
        eng, r0, rids = self._decoding_then_waiting(
            model, 2, waiting=waiting, max_batch_size=5, token_budget=20)
        first_fed, reqs = {}, None
        for launch in range(1, 10):
            eng.step()                        # the first admits all four
            reqs = reqs or [eng._active[r] for r in rids]
            for i, r in enumerate(reqs):
                if r.chunks_fed:
                    first_fed.setdefault(i, launch)
            if not any(r.in_prefill for r in reqs):
                break
        else:
            pytest.fail("a waiting prompt was starved")
        assert first_fed[0] == first_fed[1] == 1      # 1 + 8 + 8 of 20
        assert first_fed[2] > 1                       # 8 more do not fit
        assert first_fed[3] >= first_fed[2]           # and nobody overtakes
        # launch 2 still holds both tails (4 + 8 of 19); launch 3 has
        # them decoding, and the two that waited go in together
        assert (first_fed[2], first_fed[3]) == (3, 3)
        out = eng.run()
        for r, p in zip(rids, waiting):
            assert out[r] == ref_greedy(model, p, 6)

    def test_prefix_hit_shares_a_launch_with_a_fresh_prompt(self, model):
        """A fresh prompt longer than the whole budget and, behind it, one
        that a prefix hit dropped in mid-chunk (``prefill_pos`` 16, chunks
        of 6) prefill in the same launch; cache on and off give the same
        tokens, the model's own."""
        shared = list(range(30, 46))          # 16 tokens = 2 full blocks
        hit, fresh = shared + [5, 6, 8, 10, 11, 12, 13], list(range(100, 140))
        outs = {}
        for cache in (False, "auto"):
            eng = ServingEngine(model, prefix_cache=cache, megastep_k=2,
                                prefill_chunk_tokens=6,
                                **{**ENGINE, **self.SHARED})
            eng.add_request(shared + [7, 9], max_new_tokens=4)
            eng.run()                         # seeds the cache
            rd = eng.add_request([3, 17, 101], max_new_tokens=10)
            eng.step()                        # rd decoding
            r2 = eng.add_request(fresh, max_new_tokens=6)
            r1 = eng.add_request(hit, max_new_tokens=6)
            mixed = eng.megasteps_mixed
            eng.step()
            start = 16 if cache == "auto" else 0
            assert eng.megasteps_mixed == mixed + 1
            assert eng._active[r1].prefill_pos == min(start + 12, len(hit))
            assert eng._active[r2].prefill_pos == 12
            rest = eng.run()
            outs[cache] = (rest[rd], rest[r1], rest[r2])
        assert eng.prefix_hit_blocks >= 2     # the cache engaged
        assert outs[False] == outs["auto"]
        assert outs["auto"] == (ref_greedy(model, [3, 17, 101], 10),
                                ref_greedy(model, hit, 6),
                                ref_greedy(model, fresh, 6))

    def test_mixed_launch_span_carries_prefill_rows(self, model, host_spans):
        """``engine.launch`` of kind ``mixed`` says how many rows the
        launch feeds chunks; no other kind carries the attribute."""
        warm, _, _ = self._decoding_then_waiting(model, 2)
        warm.run()                            # compiles, untraced
        eng, _, _ = self._decoding_then_waiting(model, 2)
        with host_spans("engine.launch") as events:
            eng.run()
        mixed = [e[3] for e in events if e[3]["kind"] == "mixed"]
        assert [m["prefill_rows"] for m in mixed[:2]] == [3, 3]
        assert all(1 <= m["prefill_rows"] <= 3 for m in mixed)
        others = [e[3] for e in events if e[3]["kind"] != "mixed"]
        assert others and not any("prefill_rows" in o for o in others)

    def test_prefill_chunk_failpoint_fires_once_a_prompt_a_launch(self, model):
        """``engine.prefill_chunk`` is traversed before the compiled call,
        once for each prompt a mixed launch feeds: three here, the
        routing no longer fires for the first a second time."""
        from paddle_tpu.inference.faults import FaultInjector, prompt_signature

        inj = FaultInjector({"engine.prefill_chunk":
                             {"kind": "delay", "delay_s": 0.0},
                             "engine.megastep":
                             {"kind": "delay", "delay_s": 0.0}},
                            sleep=lambda s: None)
        eng, _, _ = self._decoding_then_waiting(model, 2, fault_injector=inj)
        del inj.log[:]                        # r0's own single-step prefill
        eng.step()
        assert [(site, detail) for site, _, detail in inj.log] == [
            ("engine.megastep", " ".join(
                prompt_signature(p) for p in ([3, 17, 101],) + self.WAITING)),
            *(("engine.prefill_chunk", prompt_signature(p))
              for p in self.WAITING)]


# ------------------------------------------- names, spans and phase seconds
class _Recorded:
    """Stands in for one of the engine's jitted programs: keeps the lowered
    text of its first call, and ``on_call(outputs)`` may change what the
    engine gets back."""

    def __init__(self, fn, on_call=None):
        self.fn, self.on_call, self.text = fn, on_call, None

    def __call__(self, *args, **kwargs):
        import jax

        if self.text is None:
            shapes = jax.tree_util.tree_map(
                lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                           if hasattr(x, "shape") else x), (args, kwargs))
            self.text = self.fn.lower(*shapes[0], **shapes[1]).as_text(
                debug_info=True)
        out = self.fn(*args, **kwargs)
        return self.on_call(out) if self.on_call is not None else out


# (attribute, builder): every program returns (caches, scales, result block,
# logprobs, probs), and the result block is the first thing the host reads
PROGRAMS = {"step": ("_step_fn", "_build_step"),
            "mega": ("_mega_fn", "_build_megastep"),
            "mixed": ("_mixed_fn", "_build_mixed_megastep"),
            "spec": ("_spec_fn", "_build_spec_verify")}
RESULT = 2
# what each program's lowered text has to name (PERF.md section 3: a
# per-layer metric that matches a scope reads nothing once it is gone)
# (regular expressions: attention is blocked over the context, so its three
# inner scopes lie under ``paged_attention/while/body/``, once for each loop
# around them: the row tiles or the chunk rows, then the context blocks)
IN_LOOP = "paged_attention/(?:while/body/)+"
FORWARD = ("embed", "norm", "attn_proj", "paged_attention", "attn_out", "mlp",
           "head", "sample", "paged_attention/rope", "paged_attention/kv_write",
           IN_LOOP + "kv_gather", IN_LOOP + "scores", IN_LOOP + "values",
           "paged_attention/while/body/while/body/kv_gather")
# ``scan_carry`` holds the slicing of the launch's ONE control block too
# (ISSUE 35), so every program has it, the step included; the float rows come
# out of the block by their bits
CARRY = ("scan_carry", "scan_carry/slice", "scan_carry/bitcast_convert_type")
SCOPES = {kind: FORWARD + CARRY for kind in ("step", "mega", "mixed", "spec")}


def _recorded_engine(model, on_call=None, **kw):
    """An engine whose four programs are ``_Recorded``; ``on_call(kind,
    outputs)`` sees every launch's outputs."""
    eng = ServingEngine(model, megastep_k=4, spec_k=2, **{**ENGINE, **kw})
    for kind, (attr, build) in PROGRAMS.items():
        fn = eng._programs.setdefault(kind, None) or getattr(eng, build)()
        eng._programs[kind] = fn
        setattr(eng, attr, _Recorded(
            fn, None if on_call is None else (lambda out, k=kind: on_call(k, out))))
    return eng


def _drive_all_programs(eng):
    """Prefill step, mixed scan (a prompt arrives while a row decodes),
    decode scan, and a verify launch (a repetitive prompt drafts)."""
    no_spec = SamplingParams(spec=False)
    eng.add_request([3, 17, 101], max_new_tokens=12, sampling=no_spec)
    eng.step()
    eng.add_request([40, 41, 42, 43, 44, 45, 46, 47, 48, 49], max_new_tokens=6,
                    sampling=no_spec)
    eng.run()
    eng.add_request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=48)
    eng.run()


@pytest.fixture(scope="module")
def program_texts(model):
    eng = _recorded_engine(model)
    _drive_all_programs(eng)
    return {kind: getattr(eng, attr).text for kind, (attr, _) in PROGRAMS.items()}


class TestNamesSpansAndPhases:
    @pytest.mark.parametrize("kind", sorted(PROGRAMS))
    def test_lowered_program_names_its_scopes(self, program_texts, kind):
        import re

        text = program_texts[kind]
        assert text, f"the {kind} program never ran"
        missing = [s for s in SCOPES[kind]
                   if not re.search(rf'["/(]{s}[/)"]', text)]
        assert not missing, f"{kind}: no operation under {missing}"

    @pytest.mark.parametrize("kind", sorted(PROGRAMS))
    def test_phase_seconds_on_an_injected_clock(self, model, kind):
        """``execute`` is launch + wait, and the three old keys sum to the
        steps' wall time: admission takes 1 s, a launch 4 s, the blocking
        read of its first output 2 s, nothing else any time."""
        clock = FakeClock()
        launches = []

        class SlowRead:
            def __init__(self, value):
                self.value = value

            def copy_to_host_async(self):
                pass

            def __array__(self, dtype=None, copy=None):
                clock.advance(2.0)
                return np.asarray(self.value)

        def on_call(k, out):
            clock.advance(4.0)
            launches.append(k)
            out = list(out)
            out[RESULT] = ResultBlock(SlowRead(out[RESULT].words), out[RESULT].layout)
            return tuple(out)

        eng = _recorded_engine(model, on_call, clock=clock)
        admit = eng._try_admit
        admits = []

        def slow_admit():
            # harvest admits again when a frozen row frees its slot: that
            # call is inside engine.harvest and costs nothing here
            if not admits or admits[-1] != eng.launches:
                clock.advance(1.0)
            admits.append(eng.launches)
            return admit()

        eng._try_admit = slow_admit
        t_start = clock()
        _drive_all_programs(eng)
        assert kind in launches
        ps = eng.phase_seconds
        n = len(launches)
        assert eng.launches == n
        assert ps["launch"] == pytest.approx(4.0 * n)
        assert ps["execute"] == pytest.approx(ps["launch"] + 2.0 * n)
        assert (ps["schedule"] + ps["execute"] + ps["harvest"]
                == pytest.approx(clock() - t_start))
        assert eng.state_summary()["phase_seconds"]["launch"] == ps["launch"]

    def test_spans_nest_in_order_with_their_attributes(self, model, host_spans):
        """A traced run read back through ``ProfileData``: inside each
        ``frontend.step``, ``engine.step`` holds admit, schedule, launch,
        wait, harvest in that order, and ``engine.launch`` carries kind, k,
        launch and t_mono."""
        clock = FakeClock()
        eng = ServingEngine(model, megastep_k=4, clock=clock, **ENGINE)
        fe = ServingFrontend([eng], clock=clock)
        fe.submit([3, 17, 101], max_new_tokens=2)
        fe.run()                                    # compiles, untraced
        with host_spans("frontend.", "engine.") as events:
            clock.advance(5.0)
            fe.submit([3, 17, 101], max_new_tokens=6)
            fe.step()
            fe.submit([40, 41, 42, 43, 44, 45, 46, 47, 48, 49], max_new_tokens=4)
            fe.run()

        def inside(outer, name):
            return [e for e in events if e[0] == name
                    and outer[1] <= e[1] and e[2] <= outer[2]]

        fe_steps = [e for e in events if e[0] == "frontend.step"]
        eng_steps = [e for e in events if e[0] == "engine.step"]
        assert fe_steps and len(eng_steps) == len(fe_steps)
        launches = []
        for f in fe_steps:
            (step,) = inside(f, "engine.step")
            (dispatch,) = inside(f, "frontend.dispatch")
            (deliver,) = inside(f, "frontend.deliver")
            assert dispatch[2] <= step[1] and step[2] <= deliver[1]
            parts = [e for e in events if e[0] != "engine.step"
                     and e[0].startswith("engine.")
                     and step[1] <= e[1] and e[2] <= step[2]]
            names = [e[0].split(".")[1] for e in parts]
            assert names[0] == "admit" and names.count("launch") <= 1
            if "launch" in names:
                assert names == ["admit"] + ["schedule"] * (len(names) - 4) + [
                    "launch", "wait", "harvest"]
                assert all(a[2] <= b[1] for a, b in zip(parts, parts[1:]))
                launches.append(parts[names.index("launch")][3])
        assert {l["kind"] for l in launches} >= {"step", "mixed"}
        assert [l["launch"] for l in launches] == list(
            range(launches[0]["launch"], launches[0]["launch"] + len(launches)))
        assert all(l["k"] == (1 if l["kind"] == "step" else 4) for l in launches)
        assert all(l["t_mono"] == 5.0 for l in launches)


def _harvest_counts(eng):
    """[(kind of the launch, what its ``engine.harvest`` span carries)], filled
    as the engine runs."""
    seen, kinds = [], []
    launch, phase = eng._launch_phase, eng._phase

    def launched(kind, *a, **kw):
        kinds.append(kind)
        return launch(kind, *a, **kw)

    def entered(name, **attrs):
        if name == "harvest":
            seen.append((kinds[-1], attrs, eng.attn_rows_kernel))
        return phase(name, **attrs)

    eng._launch_phase, eng._phase = launched, entered
    return seen


@pytest.fixture(scope="module")
def cpu_harvests(model):
    eng = ServingEngine(model, megastep_k=4, spec_k=2, **ENGINE)
    seen = _harvest_counts(eng)
    _drive_all_programs(eng)
    return eng, seen


class TestRowsThroughTheKernel:
    """``attn_rows_kernel``: the one-token rows an iteration sent through the
    ``paged_decode`` kernel, counted by the trunk beside the two position
    counts, added up by the engine, on the harvest span and in the summary.
    And the cache write's two: ``kv_write_tokens``, the live tokens ONE cache
    layer's write put into the pool, and ``kv_write_blocks``, the block
    pieces the row-wise ``paged_write`` kernel moved for them (0 where the
    scatter ran)."""

    @pytest.mark.parametrize("kind", sorted(PROGRAMS))
    def test_every_program_carries_it_and_it_is_zero_on_the_cpu(self, cpu_harvests, kind):
        eng, seen = cpu_harvests
        mine = [(a, total) for k, a, total in seen if k == kind]
        assert mine, f"no {kind} launch"
        for attrs, _ in mine:
            assert set(attrs) == {"attn_positions_live", "attn_positions_read",
                                  "attn_rows_kernel", "attn_chunks_kernel",
                                  "kv_write_tokens", "kv_write_blocks"}
            assert attrs["attn_rows_kernel"] == 0 < attrs["attn_positions_live"]
            assert attrs["attn_chunks_kernel"] == 0
            # the CPU's path is the scatter: tokens written, no piece moved
            assert attrs["kv_write_blocks"] == 0 < attrs["kv_write_tokens"]
        assert eng.attn_rows_kernel == 0 == eng.kv_write_blocks
        assert eng.kv_write_tokens == sum(a["kv_write_tokens"] for _, a, _ in seen)
        assert eng.state_summary()["attention"] == {
            "positions_live": eng.attn_positions_live,
            "positions_read": eng.attn_positions_read, "rows_kernel": 0, "chunks_kernel": 0,
            "kv_write_tokens": eng.kv_write_tokens, "kv_write_blocks": 0}

    def test_an_engine_steered_onto_the_chip_counts_its_decoding_rows(self, monkeypatch):
        """A bf16 model with heads of 128 and blocks of 16 is a call the
        kernel admits; with ``on_tpu`` answering yes (and the kernel in
        interpret mode) every decoding row of every scan iteration goes
        through it, and the prefill step's two chunk rows through
        ``paged_chunk``: the counters are monotone, equal the rows decoded and
        the chunk rows fed, the kernel path reads less than the XLA pass's
        tiles, and the tokens are the XLA pass's."""
        import functools

        from paddle_tpu.distributed.topology import set_hybrid_communicate_group
        from paddle_tpu.inference import serving
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.ops import paged_attention as pa
        from paddle_tpu.ops.pallas import paged_chunk as pc
        from paddle_tpu.ops.pallas import paged_decode as pd
        from paddle_tpu.ops.pallas import paged_write as pw

        set_hybrid_communicate_group(None)
        P.seed(5)
        net = LlamaForCausalLM(LlamaConfig(
            vocab_size=128, hidden_size=256, intermediate_size=256,
            num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
            max_position_embeddings=128, dtype="bfloat16"))
        net.bfloat16()
        net.eval()
        geometry = dict(max_batch_size=2, max_seq_len=96, block_size=16,
                        token_budget=32, megastep_k=4, spec_k=0)
        prompts = [[3, 17, 101, 7, 9], list(range(40, 62))]

        def run():
            eng = ServingEngine(net, **geometry)
            seen = _harvest_counts(eng)
            rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
            out = eng.run()
            return eng, seen, [out[r] for r in rids]

        plain, _, want = run()
        assert plain.attn_rows_kernel == 0 == plain.kv_write_blocks
        # the platform is asked when a program is traced: drop the traces
        # made for the CPU, and those made here once the test is over (of
        # the one jitted function that asks, not the whole process's)
        monkeypatch.setattr(serving, "_PROGRAM_CACHE", {})
        monkeypatch.setattr(pa, "on_tpu", lambda: True)
        monkeypatch.setattr(pa, "paged_decode",
                            functools.partial(pd.paged_decode, interpret=True))
        monkeypatch.setattr(pa, "paged_write",
                            functools.partial(pw.paged_write, interpret=True))
        monkeypatch.setattr(pa, "paged_chunk",
                            functools.partial(pc.paged_chunk, interpret=True))
        pa.blha_attention.clear_cache()
        try:
            eng, seen, got = run()
        finally:
            pa.blha_attention.clear_cache()
        assert got == want
        totals = [t for _, _, t in seen]
        assert totals == sorted(totals) and eng.attn_rows_kernel > 0
        assert eng.attn_rows_kernel == sum(a["attn_rows_kernel"] for _, a, _ in seen)
        # both prompts in one prefill step (chunk rows: ``paged_chunk``), then
        # each row decodes its other 8 tokens a row a scan iteration
        assert eng.attn_rows_kernel == 2 * 8
        assert eng.attn_chunks_kernel == 2 == sum(a["attn_chunks_kernel"] for _, a, _ in seen)
        assert plain.attn_chunks_kernel == 0
        assert eng.state_summary()["attention"]["chunks_kernel"] == 2
        assert eng.attn_positions_live == plain.attn_positions_live
        assert eng.attn_positions_read < plain.attn_positions_read
        assert eng.state_summary()["attention"]["rows_kernel"] == 16
        # the write: the same tokens as the scatter wrote, 5 + 22 prompt
        # tokens and 8 fed back a row; the prompts lie in one piece of 16
        # positions and in two, a token fed back in one
        assert eng.kv_write_tokens == plain.kv_write_tokens == 27 + 16
        assert eng.kv_write_blocks == 1 + 2 + 16
        assert eng.kv_write_blocks == sum(a["kv_write_blocks"] for _, a, _ in seen)
        assert eng.state_summary()["attention"]["kv_write_blocks"] == 19
