"""Speculative decoding: n-gram drafting + in-graph multi-token verify
(ISSUE 19).

Contracts under test:

* the drafter is a PURE function of one request's own token history —
  deterministic across processes (no hash-seed dependence), never
  crossing a request boundary, empty on empty/short histories, and
  capped at k;
* spec-on is TOKEN-IDENTICAL to spec-off for greedy AND seeded streams
  at spec_k=1 and spec_k=8 — the verify redraws every position with the
  request's exact ``(seed, sample_index)`` key stream, so speculation
  only changes how many forwards it takes, never which tokens come out;
* the identity survives preempt/resume (``sample_offset`` carries the
  accepted-token count), replica failover, and journal recovery;
* multi-token extension of the r12 categorical-shift test: with
  ``capture_sample_probs`` on, redrawing each committed token from the
  exposed q(x) under ``fold_in(PRNGKey(seed), i)`` reproduces the
  engine's tokens exactly — including tokens committed in multi-token
  verify bursts;
* ``SamplingParams.spec=False`` opts a request out (identical tokens,
  zero verify launches), int8 KV-quant rows are excluded at the
  scheduler, and the ``spec`` knob survives the RPC wire dict;
* r16-remain regression (ISSUE 19 satellite): a deadline-frozen row's
  slot is freed at megastep harvest, so the queue head admits into the
  freed slot within the SAME ``step()``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference import (
    RequestJournal,
    RequestStatus,
    SamplingParams,
    ServingEngine,
    ServingFrontend,
)
from paddle_tpu.inference.serving import ngram_draft

pytestmark = pytest.mark.quick

ENGINE = dict(max_batch_size=2, max_seq_len=64, block_size=8,
              token_budget=16)
SAMPLED = dict(temperature=0.8, top_k=50, top_p=0.95, seed=13)
# near-greedy sampled stream: the argmax dominates every categorical
# draw, so the greedy repetition cycles (and therefore real multi-token
# accepts) survive sampling — used where a test needs accepted > 0 on a
# SAMPLED stream
NEAR_GREEDY = dict(temperature=0.001, seed=21)
# repetitive prompts: this prompt drives the tiny greedy model into a
# recurring token cycle (verified: the n-gram drafter's accepts > 0 on
# it), the drafting showcase; the alphabets are disjoint for the
# contamination check
PROMPT_A = [1, 2, 3, 1, 2, 3, 1, 2]
PROMPT_B = [9, 4, 9, 4, 9, 4, 9, 4]
N_LONG = 48   # long enough for greedy cycles to form and accept


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


def run_engine(model, prompt, n, sampling=None, **kw):
    eng = ServingEngine(model, megastep_k=4, **{**ENGINE, **kw})
    rid = eng.add_request(prompt, max_new_tokens=n, sampling=sampling)
    return eng.run()[rid], eng


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------- drafter
class TestDrafter:
    def test_continuation_of_repeated_ngram(self):
        # history [5,6,7,5,6]: the longest repeated tail n-gram is
        # [5,6] at position 0 — the draft is its historical continuation
        assert ngram_draft([5, 6, 7, 5, 6], 3) == [7, 5, 6]
        # most-recent match wins when the pattern repeats
        assert ngram_draft([1, 2, 9, 1, 2, 8, 1, 2], 1) == [8]

    def test_edges_and_cap(self):
        assert ngram_draft([], 4) == []
        assert ngram_draft([5], 4) == []
        assert ngram_draft([5, 6], 4) == []       # no prior occurrence
        assert ngram_draft([5, 6, 7], 0) == []    # k=0
        assert ngram_draft([5, 5, 5, 5], -1) == []
        for k in range(1, 6):
            assert len(ngram_draft(PROMPT_A, k)) <= k

    def test_deterministic_across_processes(self):
        """Model-free and seed-free: a fresh interpreter with a
        different PYTHONHASHSEED computes the same drafts."""
        cases = [(PROMPT_A, 8), (PROMPT_B, 3), ([1, 2, 9, 1, 2], 4)]
        here = [ngram_draft(h, k) for h, k in cases]
        code = ("import json,sys\n"
                "from paddle_tpu.inference.serving import ngram_draft\n"
                f"cases = {cases!r}\n"
                "print(json.dumps([ngram_draft(h, k) for h, k in cases]))")
        env = {**os.environ, "PYTHONHASHSEED": "271828",
               "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), timeout=120)
        assert out.returncode == 0, out.stderr
        assert json.loads(out.stdout.strip().splitlines()[-1]) == here

    def test_no_cross_request_contamination(self, model):
        """Engine-level: each row's draft is a function of ITS history
        only — two co-resident requests over disjoint alphabets draft
        strictly inside their own alphabets, and each equals the pure
        function of its own prompt."""
        eng = ServingEngine(model, megastep_k=4, spec_k=8, **ENGINE)
        ra = eng.add_request(PROMPT_A, max_new_tokens=N_LONG)
        rb = eng.add_request(PROMPT_B, max_new_tokens=N_LONG)
        eng._try_admit()
        reqs = list(eng._active.values())
        drafts = eng._draft(reqs)
        assert drafts[ra] == ngram_draft(PROMPT_A, 8)
        assert drafts[rb] == ngram_draft(PROMPT_B, 8)
        assert drafts[ra] and set(drafts[ra]) <= set(PROMPT_A)
        assert drafts[rb] and set(drafts[rb]) <= set(PROMPT_B)


# ----------------------------------------------------------- token parity
class TestSpecParity:
    @pytest.mark.parametrize("spec_k", [1, 8])
    def test_greedy_parity_and_fewer_forwards(self, model, spec_k):
        """spec-on ≡ spec-off greedy, and on the repetitive workload the
        drafter genuinely pays: accepted tokens > 0, so the verify
        launches number strictly fewer than the committed tokens."""
        want = ref_greedy(model, PROMPT_A, N_LONG)
        off, _ = run_engine(model, PROMPT_A, N_LONG)
        assert off == want
        on, eng = run_engine(model, PROMPT_A, N_LONG, spec_k=spec_k)
        assert on == want, f"spec_k={spec_k} diverged from spec-off"
        assert eng.spec_verify_forwards > 0, "spec never armed"
        assert eng.spec_accepted_tokens > 0, "nothing accepted"
        assert eng.spec_draft_tokens >= eng.spec_accepted_tokens
        summ = eng.state_summary()["spec"]
        assert summ == {"k": spec_k,
                        "accepted": eng.spec_accepted_tokens,
                        "drafted": eng.spec_draft_tokens,
                        "verify_forwards": eng.spec_verify_forwards}

    @pytest.mark.parametrize("spec_k", [1, 8])
    def test_seeded_parity(self, model, spec_k):
        off, _ = run_engine(model, PROMPT_A, N_LONG, sampling=SAMPLED)
        on, eng = run_engine(model, PROMPT_A, N_LONG, sampling=SAMPLED,
                             spec_k=spec_k)
        assert on == off, f"spec_k={spec_k} seeded stream diverged"
        assert eng.spec_verify_forwards > 0, "spec never armed"

    def test_two_rows_batched_parity(self, model):
        """Both slots speculate in one packed verify launch; each row's
        stream is identical to its solo spec-off run."""
        want_a = ref_greedy(model, PROMPT_A, N_LONG)
        want_b = ref_greedy(model, PROMPT_B, N_LONG)
        eng = ServingEngine(model, megastep_k=4, spec_k=8, **ENGINE)
        ra = eng.add_request(PROMPT_A, max_new_tokens=N_LONG)
        rb = eng.add_request(PROMPT_B, max_new_tokens=N_LONG)
        out = eng.run()
        assert out[ra] == want_a
        assert out[rb] == want_b
        assert eng.spec_accepted_tokens > 0

    def test_per_request_opt_out(self, model):
        """sampling.spec=False on a spec_k>0 engine: identical tokens,
        zero verify launches (the scheduler never arms)."""
        want = ref_greedy(model, PROMPT_A, N_LONG)
        sp = SamplingParams(spec=False)
        out, eng = run_engine(model, PROMPT_A, N_LONG, sampling=sp,
                              spec_k=8)
        assert out == want
        assert eng.spec_verify_forwards == 0
        assert eng.spec_draft_tokens == 0

    def test_spec_rides_the_wire_dict(self):
        w = SamplingParams(spec=False).to_wire()
        assert w["spec"] is False
        assert SamplingParams.coerce(w).spec is False
        assert SamplingParams.coerce(SamplingParams().to_wire()).spec

    def test_int8_rows_excluded(self, model):
        """cache_quant='int8' decodes through the megastep, never the
        verify (the scheduler excludes quantized caches from spec)."""
        out, eng = run_engine(model, PROMPT_A, 8, spec_k=2,
                              cache_quant="int8")
        assert out == ref_greedy(model, PROMPT_A, 8)
        assert eng.spec_verify_forwards == 0

    def test_spec_k_validation(self, model):
        with pytest.raises(ValueError):
            ServingEngine(model, spec_k=-1, **ENGINE)
        with pytest.raises(ValueError):
            ServingEngine(model, prefill_chunk_tokens=0, **ENGINE)
        with pytest.raises(ValueError):
            ServingEngine(model,
                          prefill_chunk_tokens=ENGINE["block_size"] + 1,
                          **ENGINE)


# ------------------------------------------------- categorical-shift, multi
class TestMultiTokenCategoricalShift:
    def test_redraw_from_qx_reproduces_spec_committed_tokens(self, model):
        """r12 property, multi-token extension: tokens committed in
        verify BURSTS still expose one q(x) per position, and redrawing
        position i from q_i under fold_in(PRNGKey(seed), i) reproduces
        the engine's token exactly — the acceptance rule collapses to
        redraw-compare precisely because of this shift-invariance."""
        import jax
        import jax.numpy as jnp

        eng = ServingEngine(model, megastep_k=4, spec_k=8,
                            capture_sample_probs=True, **ENGINE)
        rid = eng.add_request(PROMPT_A, max_new_tokens=N_LONG,
                              sampling=NEAR_GREEDY)
        toks = eng.run()[rid]
        assert eng.spec_accepted_tokens > 0, (
            "no multi-token commit — the property was only exercised "
            "one token at a time")
        qs = eng.pop_sample_probs()[rid]
        assert len(qs) == len(toks)
        for i, (q, t) in enumerate(zip(qs, toks)):
            key = jax.random.fold_in(
                jax.random.PRNGKey(NEAR_GREEDY["seed"]), i)
            redraw = int(jax.random.categorical(
                key, jnp.log(jnp.asarray(q))))
            assert redraw == t, f"sample index {i}"

    def test_capture_does_not_change_spec_tokens(self, model):
        on, _ = run_engine(model, PROMPT_A, N_LONG, sampling=SAMPLED,
                           spec_k=8, capture_sample_probs=True)
        off, _ = run_engine(model, PROMPT_A, N_LONG, sampling=SAMPLED,
                            spec_k=8)
        assert on == off


# ----------------------------------------------------- recovery identity
class TestSpecRecoveryIdentity:
    @pytest.mark.parametrize("spec_k", [1, 8])
    def test_preempt_resume_greedy_and_seeded(self, model, spec_k):
        """Evict mid-generation, resume with prompt+generated and
        sample_offset=len(generated): the accepted-token count rides the
        generated list, so the concatenated stream equals the
        unpreempted spec-off run — greedy AND seeded."""
        for sampling in (None, SAMPLED):
            full, _ = run_engine(model, PROMPT_A, N_LONG,
                                 sampling=sampling)
            eng = ServingEngine(model, megastep_k=4, spec_k=spec_k,
                                **ENGINE)
            rid = eng.add_request(PROMPT_A, max_new_tokens=N_LONG,
                                  sampling=sampling)
            eng.step()      # prefill + first token
            eng.step()      # one spec verify (or megastep) burst
            req = eng.evict(rid)
            assert 0 < len(req.generated) < N_LONG
            assert full[:len(req.generated)] == req.generated
            rid2 = eng.add_request(
                PROMPT_A + req.generated,
                max_new_tokens=N_LONG - len(req.generated),
                sampling=sampling, sample_offset=len(req.generated))
            out = eng.run()[rid2]
            assert req.generated + out == full, (
                f"spec_k={spec_k} sampled={sampling is not None}")

    @pytest.mark.parametrize("spec_k", [1, 8])
    def test_failover_to_spec_survivor(self, model, spec_k):
        """One of two spec-armed replicas dies mid-flight: every request
        completes on the survivor with the spec-off token stream."""
        def mk():
            return ServingEngine(model, megastep_k=4, spec_k=spec_k,
                                 **ENGINE)

        fe = ServingFrontend([mk(), mk()])
        prompts = [PROMPT_A, PROMPT_B, [5, 6, 7, 5, 6], [3, 9, 3, 9, 3]]
        rids = [fe.submit(p, max_new_tokens=24) for p in prompts]
        fe.step()
        doomed = fe.replicas[1]
        assert doomed.requests, "routing should have spread the load"

        def boom():
            raise RuntimeError("injected replica failure")

        doomed.engine.step = boom
        res = fe.run()
        for rid, p in zip(rids, prompts):
            assert res[rid].ok
            assert res[rid].tokens == ref_greedy(model, p, 24)
        assert fe.metrics.counter("replica_deaths_total") == 1

    def test_journal_recovery_token_identical(self, model, tmp_path):
        """Crash mid-flight, recover onto a FRESH spec engine: journal
        replay re-prefills prompt+generated with the carried
        sample_offset, so greedy and seeded streams complete exactly."""
        reqs = [(PROMPT_A, 24, {}),
                (PROMPT_B, 24, dict(**SAMPLED))]
        ref = ServingFrontend([ServingEngine(model, megastep_k=4,
                                             **ENGINE)])
        want = []
        rr = [ref.submit(p, max_new_tokens=m, **kw) for p, m, kw in reqs]
        rres = ref.run()
        want = [rres[r].tokens for r in rr]

        j = RequestJournal(str(tmp_path / "req.wal"), fsync=False)
        fe = ServingFrontend([ServingEngine(model, megastep_k=4,
                                            spec_k=8, **ENGINE)],
                             journal=j)
        rids = [fe.submit(p, max_new_tokens=m, **kw) for p, m, kw in reqs]
        fe.step()
        fe.step()       # mid-flight "crash" (abandon)
        fe2 = ServingFrontend.recover(
            j.path, [ServingEngine(model, megastep_k=4, spec_k=8,
                                   **ENGINE)])
        res = fe2.run()
        for i, rid in enumerate(rids):
            assert res[rid].status is RequestStatus.COMPLETED
            assert res[rid].tokens == want[i], f"request {i} diverged"


# ------------------------------------------- frozen-slot reuse (satellite)
class TestFrozenSlotReuse:
    def test_queue_head_admits_into_freed_slot_same_step(self, model):
        """r16 remain: both slots freeze in-graph on their deadline
        inside one megastep; harvest frees them, and the queued request
        is admitted within the SAME step() instead of parking behind
        frozen rows until the control plane's shed."""
        clock = FakeClock()
        eng = ServingEngine(model, megastep_k=4,
                            deadline_token_seconds=1.0, clock=clock,
                            **ENGINE)
        ra = eng.add_request([3, 17, 101], max_new_tokens=30,
                             deadline_s=100.0)
        rb = eng.add_request([42, 5, 9], max_new_tokens=30,
                             deadline_s=100.0)
        eng.step()                  # prefill both + first token at t=0
        clock.t = 97.0              # 3 iteration budgets remain
        rq = eng.add_request([7, 7, 9], max_new_tokens=4)
        assert rq not in eng._active        # no free slot: still queued
        eng.step()                  # scan freezes A and B in-graph
        # frozen rows released but still active (awaiting the typed
        # shed); the queue head claimed a freed slot THIS step
        assert eng._active[ra].slot < 0 and not eng._active[ra].done
        assert eng._active[rb].slot < 0 and not eng._active[rb].done
        assert rq in eng._active and eng._active[rq].slot >= 0, (
            "queue head did not admit into the freed slot")
        for _ in range(8):
            if rq in eng._finished:
                break
            eng.step()
        assert eng.pop_finished()[rq] == ref_greedy(model, [7, 7, 9], 4)
        # the control plane's shed path (evict) re-releases safely
        for r in (ra, rb):
            req = eng.evict(r)
            assert 0 < len(req.generated) < 30

    def test_frontend_shed_still_typed_after_early_free(self, model):
        """End to end: the early slot free does not change the control
        plane's observable contract — the frozen row still turns into
        DEADLINE_EXCEEDED with zero token overshoot."""
        clock = FakeClock()
        eng = ServingEngine(model, megastep_k=4,
                            deadline_token_seconds=1.0, clock=clock,
                            **ENGINE)
        fe = ServingFrontend([eng], clock=clock)
        rid = fe.submit([3, 17, 101], max_new_tokens=30, deadline_s=100.0)
        fe.step()
        clock.t = 97.0
        fe.step()
        assert fe.result(rid) is None
        clock.t = 101.0
        fe.step()
        res = fe.result(rid)
        assert res is not None
        assert res.status is RequestStatus.DEADLINE_EXCEEDED
        assert len(res.tokens) == 4
        assert res.tokens == ref_greedy(model, [3, 17, 101], 30)[:4]
