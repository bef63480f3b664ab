"""Continuous-batching serving engine over the paged KV cache — the
TPU-native equivalent of the reference's serving decode stack
(block_multihead_attention + FusedMultiTransformer cache decode +
fused_get_padding_offset plumbing; reference:
/root/reference/python/paddle/incubate/nn/functional/block_multihead_attention.py:19,
/root/reference/python/paddle/incubate/nn/layer/fused_transformer.py:994).

Design:
- ONE compiled step program with fixed shapes: a packed token buffer
  [token_budget] carries a mix of decode tokens (1 per running sequence) and
  prefill chunks (admitted prompts are fed chunk-by-chunk). Sequences of any
  length enter and retire without recompilation — admission/eviction is pure
  host bookkeeping over the block free-list.
- KV lives in per-layer block pools indexed through per-sequence block
  tables; the served model says which arrays a layer keeps there
  (serving_model.py ``CacheSpec``): keys and values [num_blocks, KV, bs, D]
  for a per-head cache (ops/paged_attention.py), one latent entry a token
  [num_blocks, bs, W] for MLA (ops/latent_attention.py). Sampling runs
  in-graph — temperature / top-k / top-p with per-request PRNG keys and
  optional logprobs; ``temperature=0`` (the default) takes the exact
  argmax path, so greedy serving is bit-identical to the pre-sampling
  engine.  The host reads back [B] next-token ids per step (one small
  transfer, the same shape every step).
- MEGASTEP decode (ISSUE 9, mixed-phase since ISSUE 16): ``step()`` runs
  K iterations inside ONE compiled ``lax.scan`` instead of K host round
  trips — the host syncs only at megastep boundaries (finish / admission).
  ARMING RULE: the scan arms whenever any scheduled row is DECODING
  (``megastep_k > 1``).  A pure-decode batch runs the tight [B]-token
  scan (mq=1); a batch mixing decode rows with prefilling rows runs the
  MIXED scan (mq=the chunk width, block_size by default).  ``mq`` bounds
  what ONE row may feed; it is not a padding every row pays: the dense
  attention (ops/paged_attention.py) runs the rows that feed one token in
  tiles of like context length and the rows that feed a chunk one at a
  time, each against the live part of its own context, so a mixed
  iteration costs a decode iteration plus its chunks.  Each iteration
  processes, per row, either
  one decode token or one block-size prompt chunk — prompt chunks are fed
  as data through a ``prefill_pos`` carry against a host-staged prompt
  window, so chunked prefill adds no shape axis and no recompile.  A
  mixed launch books its token budget by chunk: one token a decoding
  row, then one chunk a prefilling row in admission order while chunks
  fit, so every waiting prompt that fits prefills in the same scan.  Under
  open-loop admission the megastep therefore never disarms just because
  some row is still prefilling (Sarathi/vLLM-style stall-free chunked
  prefill).  Rows that finish mid-scan (EOS or token budget) are masked:
  their carry freezes and their sampled tokens are dropped on the host.
  K rounds up to a power of two (bounded compile count) capped at
  ``megastep_k``; ``megastep_k=1`` restores per-token stepping.  The
  int8 KV cache rides the pure-decode scan too (its per-(slot, kv-head)
  scales travel in the scan carry; enc=0 rows pass them through
  untouched) — only its one-shot PREFILL keeps the single-step path,
  because dynamic scales freeze at prefill.  Per-row DEADLINE budgets
  ride the carry as data (iterations, not wall clock — compiled bodies
  never read a clock): a row whose budget hits zero freezes in-graph,
  so deadline overshoot inside a megastep is ZERO tokens once a
  per-iteration time estimate exists (``deadline_token_seconds`` or the
  engine's measured EWMA); the host-side typed shed stays the
  control plane's job at harvest (control_plane.py).
- This is the vLLM-style schedule expressed the XLA way: static shapes +
  dynamic lengths as data, not as shapes.
- Automatic prefix caching (on by default, ``prefix_cache="auto"``):
  ``BlockManager`` refcounts blocks and keeps a content-hash index chained
  over ``(parent_hash, block_size token ids)`` — a retiring or evicted
  request publishes its FULL blocks, and admission maps the longest cached
  full-block prefix of a new prompt straight into its block table with
  ``prefill_pos`` advanced past it, so the compiled step only ever feeds
  the uncached tail (``prefill_pos`` is data, not shape: no recompile, no
  in-graph change).  Granularity is whole blocks: a partial tail block is
  never shared, and a fully-cached block-aligned prompt re-feeds exactly
  one token into a copy-on-write fork of its last block (compute must see
  ≥ 1 token to produce logits; the shared original stays read-only).
  Refcount-0 published blocks park in an LRU that ``allocate`` evicts
  only when the true free list is empty.  ``cache_quant='int8'`` is
  excluded by a hard error: its per-(slot, kv-head) dynamic scales make
  block payloads writer-specific, so shared blocks would dequantize
  garbage.

Frontend → fleet → engine split: the engine is a pure execution loop —
it admits whatever is in its queue, steps, and retires.  Policy
(priority classes, deadlines, admission control, routing across replicas,
failover) lives in ``ServingFrontend`` (control_plane.py), which drives
``step()`` and harvests via ``pop_finished()``.  The frontend does not
care where an engine runs: in-process ``ServingEngine`` objects and
``fleet.RemoteReplica`` adapters (the same surface proxied over RPC to a
``tools/serving_worker.py`` process on this or another host) are
interchangeable replicas; ``fleet.ServingFleet`` spawns/drains those
workers and layers heartbeats + autoscaling on top.  The preemption contract: ``evict(rid)``
removes a queued or running request mid-flight, frees its blocks and slot
immediately (BlockManager tolerates this and guards double-frees), and
returns the request object; the caller re-queues it with ``prompt +
generated`` as the new prefill.  Greedy decode is deterministic, so a
preempted-then-resumed request reproduces the unpreempted token stream
exactly.
"""
from __future__ import annotations

import hashlib
import time
from collections import Counter, OrderedDict
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..profiler import SETUP, RecordEvent, SetupSpan
from .faults import register_failpoint
from .launch_block import Layout, ResultBlock
from .serving_model import CacheKind

__all__ = ["BlockManager", "ServingRequest", "ServingEngine",
           "SamplingParams", "prefix_block_hash", "prompt_block_hashes",
           "ngram_draft"]
# the policy layer above this engine lives in control_plane.py
# (ServingFrontend) and metrics.py (ServingMetrics)

# rolling weight swaps (ISSUE 18): fired at the top of load_weights,
# BEFORE any state is touched, so an injected swap fault leaves the old
# weights fully serving — the rolling_swap driver keeps the replica on
# its previous version and counts weight_swap_failures_total
WEIGHTS_SWAP = register_failpoint("weights.swap")

# speculative decoding (ISSUE 19): both sites DEGRADE, never corrupt —
# a drafting fault empties that row's draft (the verify still commits
# its one non-spec token), a verify fault falls the whole step back to
# the megastep/single-step path.  Either way the emitted token stream
# is bit-identical to spec-off; chaos asserts exactly that.
SPEC_DRAFT = register_failpoint("engine.spec_draft")
SPEC_VERIFY = register_failpoint("engine.spec_verify")


@dataclass
class SamplingParams:
    """Per-request decode sampling knobs, applied IN-GRAPH.

    ``temperature=0`` (default) is exact greedy argmax — bit-identical to
    the engine's historical path, which is what the preempt/resume,
    prefix-cache-parity, and chaos token-identity contracts are stated
    over.  With ``temperature > 0``: logits are scaled, the top-k then
    top-p (nucleus) filters apply, and the token is drawn with a
    per-request PRNG key derived ONLY from ``(seed, sample index)`` —
    never from batch slot, megastep size, or replica — so the same seed
    replays the same token stream across preemption, failover resume,
    and worker restarts.  ``logprobs=True`` additionally returns the
    log-softmax of the RAW logits at each sampled token (temperature- and
    filter-independent, so greedy and sampled runs report comparable
    values)."""

    temperature: float = 0.0
    top_k: int = 0          # 0 = no top-k filter
    top_p: float = 1.0      # 1.0 = no nucleus filter
    seed: int = 0
    logprobs: bool = False
    # opt OUT of speculative decoding for this request (ISSUE 19).  Only
    # effective on engines built with spec_k > 0; spec-on is token-
    # identical to spec-off by contract, so the toggle exists for
    # latency-shape control (verify batches commit tokens in bursts),
    # not correctness.
    spec: bool = True

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")
        # the seed feeds an int32 PRNG-key array inside the step program:
        # reject out-of-range here (submit time) — otherwise numpy raises
        # mid-step and the control plane reads that as a replica DEATH,
        # burning the whole retry budget on one bad user parameter
        if not 0 <= self.seed < 2 ** 31:
            raise ValueError("seed must be in [0, 2**31)")

    @classmethod
    def coerce(cls, v) -> "SamplingParams":
        if v is None:
            return cls()
        if isinstance(v, cls):
            return v
        return cls(**dict(v))   # plain dict: the RPC wire format

    def to_wire(self) -> Dict:
        """The dict form shipped over RPC (and back through ``coerce``) —
        the ONE place the field list is enumerated, so a new sampling
        knob cannot be silently dropped at a transport boundary."""
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed,
                "logprobs": self.logprobs, "spec": self.spec}


@jax.named_scope("sample")
def _sample_tokens(logits, temps, top_ks, top_ps, seeds, sample_pos,
                   return_probs: bool = False):
    """In-graph next-token selection for one batch of logits rows [B, V].

    Greedy rows (``temps <= 0``) take the exact float32 argmax the engine
    always used.  Sampled rows divide by temperature, apply top-k and
    top-p in sorted space (ties at the threshold are kept), and draw via
    ``jax.random.categorical`` under a key folded from ``(seed,
    sample_pos)``.  A ``lax.cond`` skips the two [B, V] sorts entirely
    when the whole batch is greedy, so the default serving path pays
    nothing for the sampling machinery.  Returns (next_token [B] int32,
    raw-logit logprob of that token [B] float32).

    ``return_probs=True`` (ISSUE 11 satellite; trace-time constant)
    additionally returns the renormalized POST-top-k/top-p distribution
    the token was actually drawn from, [B, V] float32 — a one-hot at the
    argmax for greedy rows — which is exactly the q(x) a speculative-
    decode verifier needs.  The drawn token is bit-identical either way
    (same filtered logits, same key; categorical is shift-invariant),
    but the probs path always computes the filter, so the all-greedy
    sort skip is forfeited — keep it off for plain serving."""
    lg = logits.astype(jnp.float32)
    B, V = lg.shape
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def _filtered(scaled):
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]            # descending
        kth = jnp.take_along_axis(
            srt, jnp.clip(top_ks - 1, 0, V - 1)[:, None], axis=-1)
        keep_k = (top_ks[:, None] <= 0) | (scaled >= kth)
        probs_srt = jax.nn.softmax(srt, axis=-1)            # sorted probs
        csum = jnp.cumsum(probs_srt, axis=-1)
        # nucleus cutoff: the prob of the first sorted token at which the
        # cumulative mass reaches p (so at least one token always stays)
        first = jnp.argmax(csum >= top_ps[:, None], axis=-1)
        cutoff = jnp.take_along_axis(probs_srt, first[:, None], axis=-1)
        probs = jax.nn.softmax(scaled, axis=-1)
        keep_p = (top_ps[:, None] >= 1.0) | (probs >= cutoff)
        return jnp.where(keep_k & keep_p, scaled, -jnp.inf)

    def _draw(filt):
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
        )(seeds, sample_pos)
        return jax.vmap(jax.random.categorical)(keys, filt).astype(jnp.int32)

    if return_probs:
        filt = _filtered(lg / jnp.maximum(temps, 1e-6)[:, None])
        nxt = jnp.where(temps <= 0.0, greedy, _draw(filt)).astype(jnp.int32)
        sample_probs = jnp.where(
            (temps <= 0.0)[:, None],
            jax.nn.one_hot(greedy, V, dtype=jnp.float32),
            jax.nn.softmax(filt, axis=-1))
        logprob = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                                      nxt[:, None], axis=-1)[:, 0]
        return nxt, logprob, sample_probs

    drawn = jax.lax.cond(jnp.all(temps <= 0.0), lambda _: greedy,
                         lambda _: _draw(
                             _filtered(lg / jnp.maximum(temps, 1e-6)[:, None])),
                         None)
    nxt = jnp.where(temps <= 0.0, greedy, drawn).astype(jnp.int32)
    logprob = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                                  nxt[:, None], axis=-1)[:, 0]
    return nxt, logprob, None


def ngram_draft(history: Sequence[int], k: int,
                max_ngram: int = 3) -> List[int]:
    """Model-free n-gram / prompt-lookup drafting (Saxena 2023): find
    the most recent EARLIER occurrence of the history's longest matching
    tail n-gram (n = ``max_ngram`` down to 1) and propose up to ``k``
    tokens of its continuation.  Pure Python over ints — deterministic,
    seed-free, and identical across processes, so replica failover and
    journal replay re-draft (and hence re-verify) the exact same
    proposals.  Operates on ONE request's ``prompt + generated`` history
    only; no cross-request state exists to contaminate.  Returns ``[]``
    when the history is empty/too short or no tail n-gram recurs —
    drafting is best-effort, the verify commits >= 1 token either way."""
    h = [int(t) for t in history]
    n_hist = len(h)
    if k <= 0 or n_hist < 2:
        return []
    for n in range(min(int(max_ngram), n_hist - 1), 0, -1):
        pat = h[-n:]
        for i in range(n_hist - n - 1, -1, -1):
            if h[i:i + n] == pat:
                return h[i + n:i + n + k]
    return []


def prefix_block_hash(parent: Optional[str], tokens: Sequence[int]) -> str:
    """Chain hash of ONE full block of token ids:
    ``blake2b(parent_hash, token bytes)``.  The chaining means a block's
    hash commits to the entire token prefix before it, so equal hashes ⇒
    equal KV content.  blake2b (not builtin ``hash``, which is randomized
    per process) keeps hashes comparable across worker processes — the
    frontend's prefix-affinity routing matches its own prompt hashes
    against hash sets shipped from remote replicas."""
    h = hashlib.blake2b(digest_size=16)
    h.update(parent.encode() if parent else b"\x00root")
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.hexdigest()


def prompt_block_hashes(tokens: Sequence[int], block_size: int) -> List[str]:
    """Chain hashes for every FULL block of ``tokens`` (a partial tail
    block is never cached or matched — it would alias every continuation
    sharing its first few tokens)."""
    out: List[str] = []
    parent = None
    for i in range(len(tokens) // block_size):
        parent = prefix_block_hash(
            parent, tokens[i * block_size:(i + 1) * block_size])
        out.append(parent)
    return out


class BlockManager:
    """Host-side refcounted allocator over the global block pool, with a
    content-hash index for automatic prefix caching.

    A block is in exactly one of three states:

    * **free**   — on the free list; the next ``allocate`` may return it.
    * **live**   — refcount ≥ 1: owned by one or more sequences.  ``fork``
      shares a live (or cached) block with another sequence read-only;
      ``free`` decrements and only releases at refcount 0.
    * **cached** — refcount 0 but content-addressable: ``publish`` gave it
      a chain hash, so when its last owner freed it, it was parked in an
      LRU instead of hard-freed.  ``lookup`` + ``fork`` revive it for a
      new sequence; ``allocate`` evicts from the LRU (oldest first,
      dropping the hash mapping) only when the true free list is empty.

    ``free`` rejects double-frees loudly: releasing a block more times
    than it has owners would hand the same block to two sequences on the
    next ``allocate`` and silently corrupt both KV streams (the failure
    mode is token garbage long after the actual bug).  Mid-flight release
    of a live request's blocks (eviction/preemption) is fine — that is
    the normal path for ``ServingEngine.evict``."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self._ref: Dict[int, int] = {}          # live blocks only
        self._hash_of: Dict[int, str] = {}      # published block -> hash
        self._block_of: Dict[str, int] = {}     # hash -> published block
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # cached, ref 0
        self.evictions = 0   # cached blocks dropped to satisfy allocate

    def can_allocate(self, n: int) -> bool:
        return len(self._free) + len(self._lru) >= n

    def allocate(self, n: int) -> List[int]:
        if not self.can_allocate(n):
            raise RuntimeError(f"block pool exhausted (need {n}, "
                               f"free {self.num_free})")
        out: List[int] = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                # true free list empty: evict the least-recently-cached
                # block (its KV becomes unreachable — drop the hash)
                b, _ = self._lru.popitem(last=False)
                h = self._hash_of.pop(b)
                del self._block_of[h]
                self.evictions += 1
            self._ref[b] = 1
            out.append(b)
        assert len(set(out)) == len(out), \
            f"free-list corruption: allocate returned duplicate ids {out}"
        return out

    def free(self, blocks: List[int]):
        counts = Counter(blocks)
        internal = sorted(b for b, c in counts.items() if c > 1)
        bad = sorted(b for b in counts if not 0 <= b < self.num_blocks)
        dup = sorted(b for b in counts
                     if 0 <= b < self.num_blocks and b not in internal
                     and self._ref.get(b, 0) < counts[b])
        if dup or internal or bad:
            raise RuntimeError(
                "BlockManager.free: "
                + "; ".join(filter(None, [
                    f"double-free of block ids {dup}" if dup else "",
                    f"ids repeated in the freed list {internal}"
                    if internal else "",
                    f"ids outside the pool {bad}" if bad else ""])))
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] > 0:
                continue          # still shared with another sequence
            del self._ref[b]
            if b in self._hash_of:
                self._lru[b] = None   # published: park evictable, reusable
            else:
                self._free.append(b)

    def fork(self, block: int):
        """Hand ``block`` to one more sequence read-only (refcount++).  A
        cached (refcount-0, LRU-parked) block is revived: pulled out of
        the LRU with refcount 1.  Forking a free block is a bug."""
        if not 0 <= block < self.num_blocks:
            raise RuntimeError(f"BlockManager.fork: id {block} outside the "
                               f"pool of {self.num_blocks}")
        if block in self._lru:
            del self._lru[block]
            self._ref[block] = 1
        elif self._ref.get(block, 0) > 0:
            self._ref[block] += 1
        else:
            raise RuntimeError(
                f"BlockManager.fork: block {block} is on the free list — "
                "only live or cached blocks can be shared")

    def lookup(self, h: str) -> Optional[int]:
        """Block currently holding the content with chain hash ``h``
        (live or cached), or None."""
        return self._block_of.get(h)

    def publish(self, block: int, h: str) -> bool:
        """Register ``block``'s content under chain hash ``h`` so a later
        ``free`` parks it in the LRU (reusable) instead of hard-freeing.
        No-op (False) when the hash is already mapped — first publisher
        wins; chained hashing guarantees the content is identical — or
        when the block already carries a hash."""
        if h in self._block_of or block in self._hash_of:
            return False
        if self._ref.get(block, 0) <= 0:
            raise RuntimeError(
                f"BlockManager.publish: block {block} is not live — publish "
                "before freeing (free() is what parks published blocks)")
        self._block_of[h] = block
        self._hash_of[block] = h
        return True

    def ref_count(self, block: int) -> int:
        return self._ref.get(block, 0)

    def cached_hashes(self) -> Set[str]:
        """Chain hashes currently content-addressable (live or cached) —
        the engine's prefix-affinity summary shipped to the frontend."""
        return set(self._block_of)

    def drop_cached(self) -> int:
        """Invalidate the content-addressed cache: evictable (refcount-0)
        published blocks return to the free list and EVERY hash mapping
        is dropped (a live publisher keeps its block but loses the hash,
        so a later ``free`` hard-frees instead of parking).  The weight-
        swap path calls this — KV computed under the old weights must
        never be matched by a new-version prompt.  Returns the number of
        hashes invalidated."""
        n = len(self._block_of)
        for b in self._lru:
            self._free.append(b)
        self._lru.clear()
        self._block_of.clear()
        self._hash_of.clear()
        return n

    @property
    def num_free(self) -> int:
        """Blocks allocatable right now: truly free plus cached-evictable.
        (Admission headroom math must see cached blocks as capacity, or a
        warm cache would look like an exhausted pool.)"""
        return len(self._free) + len(self._lru)

    @property
    def num_cached(self) -> int:
        return len(self._block_of)

    @property
    def num_evictable(self) -> int:
        return len(self._lru)


@dataclass
class ServingRequest:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    sampling: SamplingParams = field(default_factory=SamplingParams)
    # sample index of this request's FIRST new token: a preempted request
    # resumed with prompt+generated as its new prefill passes the number
    # of tokens already sampled here, so the seeded key stream continues
    # exactly where the evicted run stopped
    sample_offset: int = 0
    # tracing wire context (ISSUE 15): {"trace", "span", "parent", "rid"}
    # stamped by the frontend (rid = the FRONTEND rid); engine lifecycle
    # events (prefill done, megastep boundaries) are recorded under it
    trace: Optional[Dict] = None
    # absolute engine-clock deadline (None = no deadline): set from the
    # ``deadline_s`` admission kwarg; megastep launches convert it into
    # an in-graph iteration budget (see _deadline_budgets)
    deadline_t: Optional[float] = None
    # runtime state
    generated: List[int] = field(default_factory=list)
    logprob_values: List[float] = field(default_factory=list)
    blocks: List[int] = field(default_factory=list)
    # a further kind of cache layer each (serving_model.py ``CacheSpec.kinds``):
    # {table column: block} of that kind's pool, taken as the row moves and
    # given back behind the kind's window; and the blocks reserved there
    kind_blocks: List[Dict[int, int]] = field(default_factory=list)
    kind_reserved: List[int] = field(default_factory=list)
    prefill_pos: int = 0          # prompt tokens already cached
    cached_prefix_tokens: int = 0  # of those, tokens REUSED from the cache
    chunks_fed: int = 0           # prompt chunks fed so far (trace index)
    slot: int = -1                # batch row while active
    done: bool = False

    @property
    def in_prefill(self) -> bool:
        return self.prefill_pos < len(self.prompt)

    @property
    def context_len(self) -> int:
        return self.prefill_pos + len(self.generated)

    @property
    def cached_len(self) -> int:
        """Positions whose keys and values are written: the newest sampled
        token is fed (and cached) one step later."""
        return self.context_len - (1 if self.generated else 0)


# Process-wide cache of compiled serving programs, keyed by the static
# configuration the _build_* closures bake into the trace (model dims +
# engine geometry + quant/capture flags).  Weights, caches and rope are
# call ARGUMENTS — the trace never bakes their values, and jax.jit
# already re-specializes on argument shapes/dtypes/pytree structure —
# so every engine built with the same geometry shares one jitted
# program AND its XLA compile cache.  N engines over one model costs
# one set of multi-second compiles instead of N.
_PROGRAM_CACHE: Dict[tuple, dict] = {}


# engine.<span> -> the phase_seconds keys its seconds are added to.
# ``execute`` stays what it was, launch + wait; ``launch`` is the part of it
# in which the host works (transfers, dispatch) and the device may wait.
_PHASE_KEYS = {"admit": ("schedule",), "schedule": ("schedule",),
               "launch": ("launch", "execute"), "wait": ("execute",),
               "harvest": ("harvest",)}


class _Phase:
    """One part of an engine step, measured once: a ``RecordEvent``
    ``engine.<name>`` on the profiler's clock, and its seconds on the
    engine's injected clock added to ``phase_seconds``."""

    __slots__ = ("eng", "name", "attrs", "span", "t0", "seconds")

    def __init__(self, eng, name, attrs):
        self.eng, self.name, self.attrs = eng, name, attrs
        self.seconds = 0.0

    def __enter__(self):
        self.t0 = self.eng._clock()
        self.span = RecordEvent("engine." + self.name, **self.attrs)
        self.span.begin()
        return self

    def __exit__(self, *exc):
        self.span.end()
        self.seconds = self.eng._clock() - self.t0
        for key in _PHASE_KEYS[self.name]:
            self.eng.phase_seconds[key] += self.seconds
        return False


def head_logits(rows, weights):
    """In a program: the rows a launch samples -> their logits [n, V], by the
    weights' ``"head"`` [hidden, vocab], or where a model ties its head and
    names none, by the table ``"embed"`` [vocab, hidden] itself (contracted
    over its columns: no second matrix)."""
    if "head" in weights:
        return rows @ weights["head"]
    return jnp.einsum("ne,ve->nv", rows, weights["embed"])


def _table_names(tables: int) -> Tuple[str, ...]:
    """The block tables' rows in a control block: ``bt``, then ``bt.1``, .. a
    further kind of cache layer each."""
    return ("bt",) + tuple(f"bt.{i}" for i in range(1, tables))


def _tables_of(c: Dict, n: int):
    """In a program: the unpacked control rows -> ``bt`` as the trunk takes it,
    the one table or a tuple of them, a kind each."""
    return c["bt"] if n == 1 else tuple(c[name] for name in _table_names(n))


@lru_cache(maxsize=64)
def control_layout(kind: str, B: int, P: int, n: int = 0, tables: int = 1) -> Layout:
    """The control rows a launch of ``kind`` sends up, in block order: the
    ``[B]`` scheduling rows, the five sampling rows, then the flattened
    tails (the block table, the mixed scan's prompt window).  ``n``: the
    packed token buffer's length (``step``), the prompt window's width
    ``K x chunk`` (``mixed``), ``spec_k`` (``spec``).  ``tables``: the block
    tables, one a KIND of cache layer (serving_model.py): a spec of one kind
    has the one tail ``bt`` it always had, a further kind adds a tail
    ``bt.<i>`` behind it, still in the ONE block.  The host packs by it
    and the program slices by it: static, from numbers both already key
    their shapes on."""
    rows = {
        "step": ("enc", "dec", "now"),
        "mega": ("toks", "dec", "now", "occ_idx", "active", "remaining", "dl", "eos"),
        "mixed": ("toks", "cached", "pp", "pp0", "plen", "active", "remaining",
                  "dl", "eos"),
        "spec": ("dec", "now", "dlen"),
    }[kind]
    tails = {
        "step": (("cu", (B + 1,)), ("token_ids", (n,))),
        "mega": (("cu", (B + 1,)),),
        "mixed": (("prompt_buf", (B, n)),),
        "spec": (("cu", (B + 1,)), ("token_ids", (B * (n + 1),)), ("draft", (B, n))),
    }[kind]
    return Layout.of(
        *((name, (B,), "b" if name == "active" else "i") for name in rows),
        ("temps", (B,), "f"), ("top_ks", (B,)), ("top_ps", (B,), "f"),
        ("seeds", (B,)), ("sample_pos", (B,)), *tails,
        *((name, (B, P)) for name in _table_names(tables)))


_BUILDERS = {"step": "_build_step", "mega": "_build_megastep",
             "mixed": "_build_mixed_megastep", "spec": "_build_spec_verify"}


def _sum_counts(counts):
    """A scan's stacked per-iteration ``counts`` -> one number each."""
    return jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), counts)


def _tree_bytes(tree) -> int:
    """Bytes of the arrays in ``tree``."""
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))


def _np_dtype(name: str) -> np.dtype:
    """Numpy dtype for a cache dtype's string form.  ``bfloat16`` (and
    friends) only resolve once ml_dtypes' registrations are imported —
    jax depends on it, so the lazy import never fails in practice."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


class ServingEngine:
    """Continuous batching for a model that answers serving_model.py's
    questions (weights, cache specification, trunk, rope): single process.

    >>> eng = ServingEngine(model, max_batch_size=4, max_seq_len=256)
    >>> rid = eng.add_request([1, 5, 7], max_new_tokens=16)
    >>> outputs = eng.run()   # {rid: [token, ...]}
    """

    # data-plane listener endpoint ("host:port"), stamped by
    # blockwire.BlockWireServer when this engine serves direct
    # worker-to-worker block pulls; None = relay-only (KVFabric.pull's
    # degrade ladder skips the wire rung)
    wire_endpoint: Optional[str] = None

    @SetupSpan("engine.init")
    def __init__(self, model, max_batch_size: int = 4, max_seq_len: int = 256,
                 block_size: int = 16, token_budget: int = 32,
                 num_blocks=None, cache_dtype=None,
                 cache_quant: str = "none", prefix_cache="auto",
                 megastep_k: int = 8, fault_injector=None,
                 capture_sample_probs: bool = False,
                 trace_recorder=None,
                 deadline_token_seconds: Optional[float] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 spec_k: int = 0,
                 clock: Callable[[], float] = time.monotonic):
        from .faults import FaultInjector

        # seeded failpoint registry (faults.py): the 'engine.step' site
        # lets a chaos run crash this engine deterministically — incl.
        # poison requests via a match on the active prompts' signatures.
        # None (the default, unless PADDLE_TPU_FAULTS is set) keeps the
        # production step loop at a single attribute test of cost.
        self._faults = (fault_injector if fault_injector is not None
                        else FaultInjector.from_env())
        cfg = model.config
        self.cfg = cfg
        self.B = int(max_batch_size)
        self.T = int(token_budget)
        self.bs = int(block_size)
        self.P = (int(max_seq_len) + self.bs - 1) // self.bs  # blocks/seq
        self.max_seq_len = self.P * self.bs
        # the model says what a layer keeps in the pool (serving_model.py)
        spec = model.serving_cache_spec()
        self.cache_spec = spec
        # a pool a KIND of cache layer (one, unless the spec names more):
        # ``num_blocks`` a number for each of them, or {kind: number}
        self.kinds = spec.kinds or (CacheKind("all", spec.layers),)
        if isinstance(num_blocks, dict):
            if set(num_blocks) - {k.name for k in self.kinds}:
                raise ValueError(f"num_blocks names {sorted(num_blocks)}; the model's cache "
                                 f"kinds are {[k.name for k in self.kinds]}")
            sizes = [num_blocks.get(k.name) for k in self.kinds]
        else:
            sizes = [num_blocks] * len(self.kinds)
        self.pools = [BlockManager(int(n if n is not None else self.B * self.P))
                      for n in sizes]
        self.blocks = self.pools[0]
        nb = self.blocks.num_blocks
        self.KV, self.D = spec.kv_heads, spec.head_dim   # None: no per-head cache
        self.L = spec.layers      # CACHE layers: a looped model's are not its weights'
        if cache_quant not in ("none", "int8"):
            raise ValueError("cache_quant must be 'none' or 'int8'")
        if cache_quant == "int8" and not spec.quantizable:
            raise ValueError(
                f"cache_quant='int8' cannot be used with {type(model).__name__}: "
                + spec.why_not)
        self.cache_quant = cache_quant
        if prefix_cache not in ("auto", True, False):
            raise ValueError("prefix_cache must be 'auto', True, or False")
        if cache_quant == "int8" and prefix_cache is True:
            raise ValueError(
                "prefix_cache cannot be combined with cache_quant='int8': "
                "the int8 cache dequantizes through per-(slot, kv-head) "
                "DYNAMIC scales frozen at each sequence's own prefill, so a "
                "block's uint8 payload is only meaningful under its writer's "
                "scales — a second sequence sharing the block would "
                "dequantize garbage. Use the unquantized cache with the "
                "prefix cache, or pass prefix_cache=False")
        if not spec.blocks_are_positions and prefix_cache is True:
            raise ValueError(
                f"prefix_cache cannot be used with {type(model).__name__}: "
                + spec.why_not)
        # 'auto' = on wherever it is sound (everything but int8 and a model
        # whose blocks are not all its state: adopted blocks carry no state a
        # slot, and a published prefix has lost its window layers' blocks)
        self.prefix_cache_enabled = (cache_quant != "int8"
                                     and spec.blocks_are_positions
                                     and prefix_cache in ("auto", True))
        self.prefix_hit_blocks = 0      # full blocks reused from the cache
        self.prefix_miss_blocks = 0     # full prompt blocks that missed
        self.prefill_tokens_computed = 0  # prompt tokens actually fed
        if cache_quant == "int8" and cache_dtype is not None:
            raise ValueError(
                "cache_quant='int8' fixes the cache dtype to uint8 — don't "
                "pass cache_dtype with it")
        if cache_quant == "int8":
            # paged int8 KV (the reference's cache_int8 serving mode):
            # uint8 blocks + per-(slot, kv-head) dynamic scales refreshed by
            # the prefill rows (ops/paged_attention.py quant contract)
            cache_dtype = jnp.uint8
        elif cache_dtype is None:
            cache_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self._compute_dtype = (jnp.bfloat16 if cfg.dtype == "bfloat16"
                               else jnp.float32)

        # this engine's set-up, as ``state_summary()["setup"]`` gives it: the
        # spans of its construction and the programs its launches acquired
        self._setup_spans = [SETUP.innermost()]
        self._acquired: List[Dict] = []
        with SetupSpan("engine.init.weights") as span:
            self._weights = model.serving_weights(self._compute_dtype)
            self._rope = model.serving_rope(self.max_seq_len)
            span.note(bytes=_tree_bytes((self._weights, self._rope)))
        self._setup_spans.append(span)
        # rolling weight swaps / tenancy (ISSUE 18): a version label that
        # rides metric + trace attribution, and the model id tenant
        # routing keys on.  Both are plain host state — load_weights
        # replaces the weight pytree without touching the compiled
        # programs (model identity is NOT in _program_key).
        self.weights_version = "v0"
        self.model_id = "default"
        # for every array the model's cache layers keep, a list (a layer
        # each), or ONE array with a leading layer axis where the model's
        # layers are a loop in its program (``spec.stacked``): (keys, values)
        # [nb, KV, bs, D] for a per-head cache, (latent,) [nb, bs, W] for a
        # latent one
        self._cache_dtype = str(jnp.dtype(cache_dtype))

        def pool(shape):
            block = tuple(shape(self.bs))
            if spec.stacked:
                return jnp.zeros((self.L, nb) + block, cache_dtype)
            return [jnp.zeros((m.num_blocks,) + block, cache_dtype)
                    for kind, m in zip(self.kinds, self.pools) for _ in range(kind.layers)]

        with SetupSpan("engine.init.pool") as span:
            self.caches = tuple(pool(shape) for _, shape in spec.arrays)
            if cache_quant == "int8":
                self.cache_scales = [
                    {k: jnp.zeros((self.B, self.KV), jnp.float32)
                     for k in ("kq", "vq", "kd", "vd")} for _ in range(self.L)]
            else:
                self.cache_scales = None
            # state a slot (serving_model.py): one array a kind,
            # [layers of that kind, slots, *shape]; () for a model without
            self.slot_state = tuple(
                jnp.zeros((layers, self.B) + tuple(shape), cache_dtype)
                for _, layers, shape in spec.slot_state)
            span.note(bytes=_tree_bytes((self.caches, self.cache_scales,
                                         self.slot_state)))
        self._setup_spans.append(span)
        # a table a kind; ``block_tables`` is the first kind's
        self.kind_tables = [np.full((self.B, self.P), -1, np.int32) for _ in self.kinds]
        self.block_tables = self.kind_tables[0]
        # further kinds: blocks reserved by admitted rows, blocks given back
        # behind a window (monotone), times the queue's head waited on the pool
        self.kind_reserved = [0] * len(self.kinds)
        self.window_blocks_released = 0
        self.admission_waits = [0] * len(self.kinds)

        # capture the renormalized post-top-k/top-p distribution each
        # drawn token was sampled from (ISSUE 11 satellite — speculative-
        # decode verification needs q(x), not just the drawn token);
        # engine-local debug/verification knob: costs the [B,V] filter
        # even for greedy batches and is not mirrored over fleet RPC
        self.capture_sample_probs = bool(capture_sample_probs)
        self._queue: List[ServingRequest] = []
        self._active: Dict[int, ServingRequest] = {}
        self._finished: Dict[int, List[int]] = {}
        self._emitted_logprobs: Dict[int, List[float]] = {}
        self._emitted_sample_probs: Dict[int, List[np.ndarray]] = {}
        self._next_rid = 0
        self._free_slots = list(range(self.B - 1, -1, -1))
        # megastep decode: K compiled iterations per host round trip
        # whenever any scheduled row is decoding (1 = per-token stepping);
        # prefilling rows ride the same scan chunk-by-chunk (mixed phase),
        # and int8 KV-quant rides the pure-decode scan with its scales in
        # the carry
        if int(megastep_k) < 1:
            raise ValueError("megastep_k must be >= 1")
        self.megastep_k = int(megastep_k)
        self.megasteps = 0          # megastep program launches (monotone)
        self.megastep_tokens = 0    # tokens emitted via the megastep path
        self.megasteps_mixed = 0    # of those launches, mixed-phase scans
        self.prefill_chunks = 0     # prompt chunks fed inside mixed scans
        # what a model's trunk counts (``counts`` of serving_model.py), added
        # up launch by launch (monotone): tokens through expert layers, and
        # the picks among them that fell on an expert held here; the context
        # positions the iterations' rows had to attend, and the positions
        # the dense paged attention read for them (ops/paged_attention.py
        # ``attention_positions``: read / live is what its tiles and context
        # blocks round up), and the one-token rows among them that went
        # through the ``paged_decode`` kernel (0 where the XLA pass ran); the
        # live tokens whose keys and values ONE cache layer's write put into
        # the pool, and the block pieces the row-wise ``paged_write`` kernel
        # brought and put back for them (0 where the scatter ran, which
        # walks the packed buffer whatever is live)
        self.moe_tokens = 0
        self.moe_local_picks = 0
        # of those picks, the ones that went through the grouped product
        # (``held_experts``: ops/pallas/expert_gmm.py; 0 where the tile loop ran)
        self.expert_rows_grouped = 0
        # an expert layer's tile loop (ops/held_experts.py ``held_experts``,
        # where the trunk counts it): held experts that got at least one row,
        # summed over layers and iterations; the tiles in use (over
        # ``experts_touched``, tiles an expert: 1 where the layout of the
        # sorted rows fits the routing); the rows the tiles multiplied, and
        # those among them that were a live pick
        self.experts_touched = 0
        self.expert_tiles = 0
        self.expert_tile_rows = 0
        self.expert_tile_rows_live = 0
        # state a slot: (row, layer) pairs whose state an iteration advanced
        self.conv_rows_fed = 0
        self.attn_positions_live = 0
        self.attn_positions_read = 0
        self.attn_rows_kernel = 0
        # chunk rows (``now > 1``) an iteration that attended in the ``paged_chunk``
        # kernel (ops/paged_attention.py ``chunks_in_kernel``; 0 on the XLA pass)
        self.attn_chunks_kernel = 0
        # a trunk's counts whose names carry a kind (``attn_positions_read.window``:
        # ONE layer of that kind's), and ``window_positions_spared`` (live context
        # one window layer did not read): {name: total}, monotone
        self.kind_counts: Dict[str, int] = {}
        # a latent cache's rows an iteration whose blocked pass ran in the
        # ``latent_rows`` kernel (ops/latent_attention.py ``rows_taken``):
        # one-token rows and chunk rows (0 where the XLA loops ran)
        self.latent_rows_kernel = 0
        self.latent_chunks_kernel = 0
        self.kv_write_tokens = 0
        self.kv_write_blocks = 0
        # learned sparse attention (ops/sparse_index.py), ONE layer's count an
        # iteration over the live queries whose context exceeds the model's
        # ``index_topk``: such queries, the context positions the indexer
        # scored for them, the positions it selected, and the latent entries
        # the attention pass brought for them
        self.dsa_queries = 0
        self.dsa_positions_scored = 0
        self.dsa_positions_selected = 0
        self.dsa_positions_read = 0
        # a model that runs its layers in several passes over the same
        # weights: tokens fed to its trunk, and tokens x passes run (``passes``
        # times the first until a token is ever let out of a pass)
        self.loop_tokens = 0
        self.loop_token_passes = 0
        # prefill chunk size (ISSUE 19 satellite, first rung toward
        # Sarathi-style budget-adaptive chunking): tokens per prompt
        # chunk inside the mixed-phase scan.  Default = block_size (the
        # historical behavior); <= block_size keeps one chunk inside one
        # KV block's worth of writes.  Trace-shaping (the scan's packed
        # chunk width), hence part of _program_key.
        pc = self.bs if prefill_chunk_tokens is None else int(prefill_chunk_tokens)
        if not 1 <= pc <= self.bs:
            raise ValueError(
                f"prefill_chunk_tokens={pc} must be in [1, block_size="
                f"{self.bs}]")
        self.pc = pc
        # speculative decoding (ISSUE 19): n-gram drafts of up to spec_k
        # tokens per pure-decode row, verified (and committed) by ONE
        # batched forward.  0 (default) disarms the path entirely.
        if int(spec_k) < 0:
            raise ValueError("spec_k must be >= 0")
        if int(spec_k) > 0 and not spec.blocks_are_positions:
            raise ValueError(
                f"spec_k > 0 cannot be used with {type(model).__name__}: "
                + spec.why_not)
        self.spec_k = int(spec_k)
        self.spec_accepted_tokens = 0   # draft tokens committed (monotone)
        self.spec_draft_tokens = 0      # draft tokens proposed (monotone)
        self.spec_verify_forwards = 0   # rows scored by verify launches
        # in-graph deadline budgets: seconds one scan iteration costs.
        # An explicit deadline_token_seconds pins it (tests, or operators
        # who measured their hardware); None lets the engine learn an
        # EWMA from measured megastep execute time.  Until some estimate
        # exists, deadline rows fall back to the K-1 boundary bound.
        if deadline_token_seconds is not None and deadline_token_seconds <= 0:
            raise ValueError("deadline_token_seconds must be > 0")
        self._tau_override = deadline_token_seconds is not None
        self._tau = (float(deadline_token_seconds)
                     if deadline_token_seconds is not None else None)
        # per-request tracing (ISSUE 15): an optional FlightRecorder ring.
        # None (the default) keeps every hook at a single attribute test —
        # same zero-cost pattern as self._faults above.
        self.trace_recorder = trace_recorder
        self._clock = clock
        # cumulative host-side seconds per step phase (schedule = admission
        # + batch marshalling, execute = compiled call + device sync,
        # harvest = token/unblocking bookkeeping; launch = the part of
        # execute before the blocking reads: transfers and dispatch);
        # written by _phase alone, surfaced via state_summary()
        self.phase_seconds = {"schedule": 0.0, "execute": 0.0, "harvest": 0.0,
                              "launch": 0.0}
        self.launches = 0           # compiled-program launches (monotone)
        # crossings of the host-device boundary (monotone): control arrays a
        # launch sent up with its dispatch, blocking reads its wait made
        self.control_arrays_up = 0
        self.result_reads = 0
        # Programs are shared process-wide across engines with identical
        # trace-shaping config (see _PROGRAM_CACHE): a fresh engine over
        # an already-served geometry starts with warm compile caches.
        with SetupSpan("engine.init.programs") as span:
            self._programs = _PROGRAM_CACHE.setdefault(self._program_key(), {})
            span.note(shared="step" in self._programs)
            if "forward" not in self._programs:
                fwd, trunk = self._build_forward(model)
                self._programs["forward"] = fwd
                self._programs["trunk"] = trunk
            self._forward = self._programs["forward"]
            self._trunk = self._programs["trunk"]
            if "step" not in self._programs:
                self._programs["step"] = self._build_step()
            self._step_fn = self._programs["step"]
            self._mega_fn = self._programs.get("mega")    # lazy: pure-decode scan
            self._mixed_fn = self._programs.get("mixed")  # lazy: mixed-phase scan
            self._spec_fn = self._programs.get("spec")    # lazy: spec verify
            self._cow_fn = self._programs.get("cow")      # lazy: COW block copy
            self._put_fn = self._programs.get("put")      # lazy: block import write
        self._setup_spans.append(span)

    def _program_key(self) -> tuple:
        """Everything the compiled-program closures capture that shapes
        the trace.  Model identity is deliberately NOT part of the key:
        weights/caches/rope enter as arguments, so jit keys their
        shapes/dtypes (and the layer count, via pytree structure)
        itself — two models with the same architecture share programs."""
        return (self.B, self.T, self.bs, self.cache_spec.key, self.cache_quant,
                bool(self.capture_sample_probs), self.pc, self.spec_k, self.P)

    @property
    def _reach(self) -> int:
        """The most positions ONE launch advances a row by: the single step's
        whole budget, a scan's ``megastep_k`` chunks."""
        return max(self.T, self.megastep_k * self.pc)

    def _kind_hold(self, k: int, need: int) -> int:
        """The most blocks of kind ``k`` a row of ``need`` blocks holds at once."""
        w = self.kinds[k].window
        return need if w is None else min(need, -(-(w + self._reach) // self.bs) + 1)

    def _table_rows(self) -> Dict[str, np.ndarray]:
        """The block tables as a control block's rows (``control_layout``)."""
        return dict(zip(_table_names(len(self.kinds)), self.kind_tables))


    def program_caches(self) -> tuple:
        """The ``caches`` argument of every program: the pool arrays, then
        the arrays of state a slot (none for a model without)."""
        return tuple(self.caches) + self.slot_state

    @property
    def key_caches(self):
        """A per-head cache's keys (``caches[0]``): a layer each, or
        ``[layers, ...]`` where the pool is stacked."""
        return self.caches[0]

    @key_caches.setter
    def key_caches(self, value):
        self.caches = (value,) + tuple(self.caches[1:])

    @property
    def value_caches(self):
        return self.caches[1]

    @value_caches.setter
    def value_caches(self, value):
        self.caches = (self.caches[0], value) + tuple(self.caches[2:])

    # ------------------------------------------------------------ weights
    def load_weights(self, model, version: Optional[str] = None,
                     model_id: Optional[str] = None) -> str:
        """Swap in ``model``'s weights WITHOUT recompiling: weights enter
        the compiled programs as call arguments, so same-architecture
        models reuse every cached program (``_program_key`` excludes
        model identity on purpose).  The caller (``rolling_swap`` or
        tenant swap-on-demand routing) is responsible for draining the
        engine first — active sequences would otherwise continue under
        the new weights mid-stream.

        The prefix cache is invalidated: cached KV was computed under
        the old weights and must never be matched by a new-version
        prompt.  Any fault (the ``weights.swap`` failpoint, a geometry
        mismatch) raises BEFORE state changes — the engine keeps serving
        the old version intact.  Returns the new version label."""
        if self._faults is not None:
            self._faults.fire(WEIGHTS_SWAP,
                              detail=str(version or model_id or ""))
        spec = model.serving_cache_spec()
        if (spec.key, spec.layers) != (self.cache_spec.key, self.L):
            raise ValueError(
                "load_weights: new model's geometry (its cache "
                "specification's key and layers) must match the engine's — "
                "the compiled step programs bake the attention geometry; "
                "boot a fresh engine for a different architecture")
        new = model.serving_weights(self._compute_dtype)   # raises before any mutation
        self._weights = new
        self.blocks.drop_cached()
        if model_id is not None:
            self.model_id = str(model_id)
        if version is not None:
            self.weights_version = str(version)
        elif model_id is not None:
            # a model swap without an explicit version still must not
            # keep the old label (metrics/parity would lie about what
            # generated the tokens)
            self.weights_version = str(model_id)
        return self.weights_version

    # ------------------------------------------------------- compiled step
    def _build_forward(self, model):
        """(forward, trunk): the model's own trunk over (weights, caches,
        packed batch), and the trunk headed at each slot's LAST packed token:
        ``forward`` heads one row a slot, the spec-verify program (ISSUE 19)
        heads every draft position: one set of layer math, two consumers."""
        trunk = model.serving_trunk(block_size=self.bs, cache_quant=self.cache_quant)

        def forward(weights, caches, rope, token_ids, enc, dec, now, cu, bt,
                    mq, scales=None):
            hidden, caches, new_scales, counts = trunk(
                weights, caches, rope, token_ids, enc, dec, now, cu, bt, mq,
                scales)
            # one logits row per batch slot: its LAST packed token
            with jax.named_scope("head"):
                rows = jnp.clip(cu[1:] - 1, 0, token_ids.shape[0] - 1)
                logits = head_logits(hidden[rows], weights)  # [B, V]
            return logits, caches, new_scales, counts

        return forward, trunk

    def _step_raw(self, weights, key_caches, value_caches, rope, token_ids,
                  enc, dec, now, cu, bt, mq, scales=None):
        """Undonated greedy step body (in-graph benching/scans keep the
        historical (nxt, kcs, vcs, scales) contract)."""
        logits, (kcs, vcs), ns, _ = self._forward(
            weights, (key_caches, value_caches), rope, token_ids, enc, dec,
            now, cu, bt, mq, scales)
        nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
        return nxt, kcs, vcs, ns

    def _build_step(self):
        """Every program takes ONE control ``block`` (``control_layout``)
        beside weights, caches, rope and the int8 scales, slices it first
        thing, and returns ``(caches, scales, result, logprobs, probs)``:
        ``result`` a :class:`ResultBlock` of everything the host always
        reads, ``logprobs`` read only when a scheduled row asked."""
        fwd = self._forward
        B, P = self.B, self.P
        with_probs = self.capture_sample_probs
        NT = len(self.kinds)
        fixed = control_layout("step", B, P, 0, NT).size

        def step(weights, caches, rope, block, scales=None, *, mq):
            with jax.named_scope("scan_carry"):
                c = control_layout("step", B, P, block.shape[0] - fixed, NT).unpack(block)
            logits, caches, new_scales, counts = fwd(
                weights, caches, rope, c["token_ids"], c["enc"], c["dec"],
                c["now"], c["cu"], _tables_of(c, NT), mq, scales)
            nxt, logprob, probs = _sample_tokens(
                logits, c["temps"], c["top_ks"], c["top_ps"], c["seeds"],
                c["sample_pos"], return_probs=with_probs)
            with jax.named_scope("scan_carry"):
                res = ResultBlock.of({"toks": nxt}, counts)
            return caches, new_scales, res, logprob, probs

        return jax.jit(step, donate_argnums=(1,), static_argnames=("mq",))

    def _build_megastep(self):
        """K decode iterations inside one compiled ``lax.scan``: the
        pure-decode megastep program.  Per-row masking implements early
        exit — a row whose sequence finishes (EOS / budget) freezes its
        carry (token, cache position, sample index), so every later
        iteration re-feeds the same token at the same position and
        rewrites the SAME KV bits (deterministic fn of token, position,
        weights), while its sampled outputs are marked invalid and
        dropped on the host.  Rows with ``now=0`` (empty batch slots)
        never write at all.  Two ISSUE 16 carry threads: ``dl`` is the
        per-row deadline budget in ITERATIONS (a row freezes the moment
        it hits 0 — zero-token overshoot, checked in-graph as data, no
        clock in the compiled body), and ``scales`` carries the int8
        KV-quant per-(slot, kv-head) scale pytree — enc=0 decode rows
        pass the values through blha untouched, but quantize writes /
        dequantize reads with them, so ``cache_quant='int8'`` rides the
        same scan instead of keeping a per-token path."""
        fwd = self._forward
        B, P = self.B, self.P
        with_probs = self.capture_sample_probs
        NT = len(self.kinds)
        layout = control_layout("mega", B, P, 0, NT)
        n_pool = len(self.cache_spec.arrays)
        slot_state = bool(self.cache_spec.slot_state)

        def mega(weights, caches, rope, block, scales=None, *, K):
            with jax.named_scope("scan_carry"):
                c = layout.unpack(block)
            toks, dec, now, cu, occ_idx = (
                c[n] for n in ("toks", "dec", "now", "cu", "occ_idx"))
            bt = _tables_of(c, NT)
            active, remaining, dl, eos = (
                c[n] for n in ("active", "remaining", "dl", "eos"))
            temps, top_ks, top_ps, seeds, sample_pos = (
                c[n] for n in ("temps", "top_ks", "top_ps", "seeds", "sample_pos"))
            enc = jnp.zeros((B,), jnp.int32)

            def body(carry, _):
                (toks, caches, dec, active, remaining, sample_pos, dl,
                 scales) = carry
                with jax.named_scope("scan_carry"):
                    packed = toks[occ_idx]    # slot-order -> packed layout
                before = caches[n_pool:]
                logits, caches, ns, counts = fwd(
                    weights, caches, rope, packed, enc, dec, now, cu, bt, 1,
                    scales)
                scales = ns if scales is not None else None
                if slot_state:
                    # a frozen row is fed its token again at the same
                    # position: the pool takes the same bits, state a slot
                    # would advance, so it keeps what it had
                    with jax.named_scope("scan_carry"):
                        keep = active & (dl > 0)
                        caches = tuple(caches[:n_pool]) + tuple(
                            jnp.where(keep.reshape((1, B) + (1,) * (a.ndim - 2)), a, b)
                            for a, b in zip(caches[n_pool:], before))
                nxt, lps, probs = _sample_tokens(
                    logits, temps, top_ks, top_ps, seeds, sample_pos,
                    return_probs=with_probs)
                with jax.named_scope("scan_carry"):
                    # a row is ALIVE while unfinished and inside its deadline
                    # budget; deadline-frozen rows stay active host-side (the
                    # control plane finalizes the typed shed at harvest) but
                    # emit nothing and advance nothing in-graph
                    alive = active & (dl > 0)
                    valid = alive
                    fin = alive & ((nxt == eos) | (remaining <= 1))
                    adv = alive & jnp.logical_not(fin)
                    # freeze finished/frozen rows: token/position/sample-index
                    # only advance while the row stays alive
                    toks = jnp.where(adv, nxt, toks)
                    dec = dec + adv.astype(jnp.int32)
                    remaining = remaining - alive.astype(jnp.int32)
                    sample_pos = sample_pos + alive.astype(jnp.int32)
                    dl = dl - alive.astype(jnp.int32)
                    active = active & jnp.logical_not(fin)
                return ((toks, caches, dec, active, remaining,
                         sample_pos, dl, scales),
                        (nxt, valid, lps, probs, counts))

            carry0 = (toks, caches, dec, active,
                      remaining, sample_pos, dl, scales)
            carry, (toks_o, valid_o, lps_o, probs_o, counts_o) = jax.lax.scan(
                body, carry0, None, length=K)
            with jax.named_scope("scan_carry"):
                res = ResultBlock.of({"toks": toks_o, "valid": valid_o},
                                     _sum_counts(counts_o))
            return carry[1], carry[7], res, lps_o, probs_o

        return jax.jit(mega, static_argnames=("K",), donate_argnums=(1,))

    def _build_mixed_megastep(self):
        """K MIXED-PHASE iterations inside one compiled ``lax.scan``:
        each iteration processes, per row, either ONE decode token or ONE
        prompt chunk of up to ``block_size`` tokens — so the megastep
        stays armed while prompts are still prefilling and open-loop
        admission never degrades decode back to per-token host stepping.

        Prompt chunks are pure data: the host stages a per-row prompt
        window ``prompt_buf[b] = prompt[pp0_b : pp0_b + K*block_size]``
        (zero-padded) and the scan slices the next chunk at offset
        ``pp - pp0`` from the ``prefill_pos`` carry.  Each iteration the
        per-row token counts are EXACT-packed into the [token_budget]
        buffer with an in-graph cumsum + scatter, so the forward's
        last-packed-token logits extraction (``cu[1:] - 1``) works
        unchanged; the attention runs with ``mq=block_size``, which only
        the rows that feed a chunk use (one at a time).  No shape
        depends on which rows are prefilling — no recompile axes beyond
        the existing static K.

        Carry per row: next decode token, KV caches, ``cached`` (tokens
        written to KV = the blha ``dec`` argument, identical bookkeeping
        for both phases), ``pp`` (prefill position), active/remaining/
        sample-index masks, and the ``dl`` deadline iteration budget
        (same zero-overshoot freeze as the pure-decode scan — prefill
        chunks burn budget too).  A row emits a token only on decode
        iterations and on the iteration that FINISHES its prefill (the
        chunk's last packed token produces the first sampled token).
        int8 is excluded here by the scheduler: dynamic quant scales
        freeze at one-shot prefill, which chunking would violate."""
        fwd = self._forward
        B, T, C, P = self.B, self.T, self.pc, self.P
        with_probs = self.capture_sample_probs
        NT = len(self.kinds)

        def mixed(weights, caches, rope, block, scales=None, *, K):
            if scales is not None:
                raise ValueError("the mixed scan carries no int8 scales")
            with jax.named_scope("scan_carry"):
                c = control_layout("mixed", B, P, K * C, NT).unpack(block)
            toks, cached, pp, pp0, plen, prompt_buf = (
                c[n] for n in ("toks", "cached", "pp", "pp0", "plen",
                               "prompt_buf"))
            bt = _tables_of(c, NT)
            active, remaining, dl, eos = (
                c[n] for n in ("active", "remaining", "dl", "eos"))
            temps, top_ks, top_ps, seeds, sample_pos = (
                c[n] for n in ("temps", "top_ks", "top_ps", "seeds", "sample_pos"))
            enc = jnp.zeros((B,), jnp.int32)

            def chunk_at(row, start):
                return jax.lax.dynamic_slice(row, (start,), (C,))

            def body(carry, _):
                (toks, caches, cached, pp, active, remaining,
                 sample_pos, dl) = carry
                with jax.named_scope("scan_carry"):
                    alive = active & (dl > 0)
                    prefilling = pp < plen
                    n_pre = jnp.minimum(plen - pp, C)
                    now_t = jnp.where(
                        alive, jnp.where(prefilling, n_pre, 1), 0
                    ).astype(jnp.int32)
                    cu = jnp.concatenate(
                        [jnp.zeros((1,), jnp.int32),
                         jnp.cumsum(now_t).astype(jnp.int32)])
                    # per-row tokens this iteration [B, C]: the next prompt
                    # chunk for prefilling rows, the carried token at column
                    # 0 for decode rows
                    chunk = jax.vmap(chunk_at)(prompt_buf, pp - pp0)
                    dec_row = jnp.zeros((B, C), jnp.int32).at[:, 0].set(toks)
                    row_toks = jnp.where(prefilling[:, None], chunk, dec_row)
                    # exact-pack into the [T] buffer (scatter; OOB -> drop):
                    # slot b's tokens land at cu[b] .. cu[b]+now_t[b]-1, so
                    # the packed layout is identical to the single-step path
                    j = jnp.arange(C, dtype=jnp.int32)[None, :]
                    flat = jnp.where(j < now_t[:, None], cu[:-1][:, None] + j,
                                     T)
                    buf = jnp.zeros((T,), jnp.int32).at[flat.reshape(-1)].set(
                        row_toks.reshape(-1), mode="drop")
                logits, caches, _, counts = fwd(
                    weights, caches, rope, buf, enc, cached, now_t, cu, bt, C,
                    None)
                nxt, lps, probs = _sample_tokens(
                    logits, temps, top_ks, top_ps, seeds, sample_pos,
                    return_probs=with_probs)
                with jax.named_scope("scan_carry"):
                    # a row emits on decode iterations and on the iteration
                    # whose chunk finishes the prompt (its last packed token
                    # is the prompt's last token -> first sampled token)
                    finishing = prefilling & (pp + n_pre >= plen)
                    emits = alive & (jnp.logical_not(prefilling) | finishing)
                    fin = emits & ((nxt == eos) | (remaining <= 1))
                    adv = emits & jnp.logical_not(fin)
                    toks = jnp.where(adv, nxt, toks)
                    cached = cached + now_t
                    pp = pp + jnp.where(alive & prefilling, n_pre, 0)
                    remaining = remaining - emits.astype(jnp.int32)
                    sample_pos = sample_pos + emits.astype(jnp.int32)
                    dl = dl - alive.astype(jnp.int32)
                    active = active & jnp.logical_not(fin)
                return ((toks, caches, cached, pp, active, remaining,
                         sample_pos, dl), (nxt, emits, lps, probs, counts))

            carry0 = (toks, caches, cached, pp, active,
                      remaining, sample_pos, dl)
            carry, (toks_o, emits_o, lps_o, probs_o, counts_o) = jax.lax.scan(
                body, carry0, None, length=K)
            with jax.named_scope("scan_carry"):
                res = ResultBlock.of(
                    {"toks": toks_o, "valid": emits_o, "pp": carry[3]},
                    _sum_counts(counts_o))
            return carry[1], None, res, lps_o, probs_o

        return jax.jit(mixed, static_argnames=("K",),
                       donate_argnums=(1,))

    def _build_spec_verify(self):
        """Score all ``spec_k + 1`` positions of every row's
        ``[last_token, draft_0 .. draft_{d-1}]`` feed in ONE batched
        forward and redraw each position with the EXACT key stream the
        non-spec path would use (greedy rows argmax; sampled rows
        ``categorical(fold_in(PRNGKey(seed), spos + j))`` over the same
        renormalized post-top-k/top-p q(x)).  Because the engine's redraw
        is deterministic, the Leviathan accept rule collapses to prefix
        matching: position j accepts iff its redraw EQUALS the draft, so
        the committed tokens are simply the redraw matrix's first
        ``accepted + 1`` columns — spec-on is token-identical to spec-off
        by construction, greedy and seeded.

        KV rewind is free, by the same argument the megastep scan uses
        to freeze finished rows: draft tokens write KV speculatively at
        ``dec .. dec+d``, the host advances ``dec`` only by the COMMITTED
        count, and a cache write is a deterministic function of (token,
        position, weights) — so accepted positions hold exactly the bits
        a non-spec feed would write, while rejected positions are
        overwritten by the next feed before any attention read reaches
        them (blha attends only up to the declared ``dec + now``).
        Prefix publishing never exposes stale bits either: it covers
        only committed-history-minus-last-token full blocks.

        The packed buffer is its OWN shape, [B * (spec_k+1)] — the trunk
        does not bake a packed length, and ``mq = spec_k + 1`` is the
        multi-token decode-extend case the mixed scan already exercises
        (every drafting row is a chunk row of the dense attention: one
        at a time, PERF.md section 6, PR 27).
        int8 KV-quant is excluded by the scheduler (same dynamic-scale
        one-shot contract that excludes it from chunked prefill)."""
        trunk = self._trunk
        B, sk = self.B, self.spec_k
        Kp1 = sk + 1
        with_probs = self.capture_sample_probs
        NT = len(self.kinds)
        layout = control_layout("spec", B, self.P, sk, NT)

        def spec_verify(weights, caches, rope, block, scales=None):
            if scales is not None:
                raise ValueError("the verify program carries no int8 scales")
            with jax.named_scope("scan_carry"):
                c = layout.unpack(block)
            token_ids, dec, now, cu, dlen, draft = (
                c[n] for n in ("token_ids", "dec", "now", "cu", "dlen", "draft"))
            bt = _tables_of(c, NT)
            temps, top_ks, top_ps, seeds, spos = (
                c[n] for n in ("temps", "top_ks", "top_ps", "seeds", "sample_pos"))
            enc = jnp.zeros((B,), jnp.int32)
            hidden, caches, _, counts = trunk(
                weights, caches, rope, token_ids, enc,
                dec, now, cu, bt, Kp1, None)
            # per-slot per-position logits rows: position j of slot b is
            # packed token cu[b] + j; rows whose draft is shorter than
            # spec_k clamp to their last fed token (masked out of the
            # accept below, so the garbage never commits)
            with jax.named_scope("head"):
                j = jnp.arange(Kp1, dtype=jnp.int32)[None, :]
                idx = jnp.clip(
                    cu[:-1][:, None] + jnp.minimum(j, dlen[:, None]),
                    0, token_ids.shape[0] - 1)
                lg = head_logits(hidden[idx.reshape(-1)], weights).reshape(
                    B, Kp1, -1)
            # redraw every position under the non-spec key stream (the
            # sample index advances by exactly one per position; Kp1 is
            # a small static constant, so a host loop over positions
            # keeps _sample_tokens' all-greedy cond a real cond)
            nxts, lpss, prbs = [], [], []
            for jj in range(Kp1):
                n_j, l_j, p_j = _sample_tokens(
                    lg[:, jj], temps, top_ks, top_ps, seeds, spos + jj,
                    return_probs=with_probs)
                nxts.append(n_j)
                lpss.append(l_j)
                if p_j is not None:
                    prbs.append(p_j)
            with jax.named_scope("scan_carry"):
                nxt = jnp.stack(nxts, axis=1)                    # [B, Kp1]
                lps = jnp.stack(lpss, axis=1)                    # [B, Kp1]
                probs = jnp.stack(prbs, axis=1) if prbs else None
                # accepted = longest draft prefix the redraw reproduces
                jk = jnp.arange(sk, dtype=jnp.int32)[None, :]
                match = (nxt[:, :sk] == draft) & (jk < dlen[:, None])
                acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                              axis=1).astype(jnp.int32)
                res = ResultBlock.of({"toks": nxt, "acc": acc}, counts)
            return caches, None, res, lps, probs

        return jax.jit(spec_verify, donate_argnums=(1,))

    # ------------------------------------------------------------- serving
    def add_request(self, prompt_ids, max_new_tokens: int = 32,
                    eos_token_id: Optional[int] = None,
                    sampling=None, sample_offset: int = 0,
                    trace: Optional[Dict] = None,
                    deadline_s: Optional[float] = None) -> int:
        """Queue one request.  ``sampling`` is a :class:`SamplingParams`
        (or its dict wire form; None = greedy argmax).  ``sample_offset``
        is the sample index of the first NEW token — a resumed request
        (prompt+generated re-prefilled after preemption/failover) passes
        the number of tokens already sampled so the seeded key stream
        continues exactly where it stopped.  ``deadline_s`` (seconds
        from now, this engine's clock) arms the IN-GRAPH deadline
        budget: megastep launches convert the remaining time into a scan
        iteration budget and the row freezes in-graph the moment it is
        spent — zero tokens of overshoot once a per-iteration estimate
        exists.  The engine only ever FREEZES on deadline; the typed
        shed (DEADLINE_EXCEEDED) stays the control plane's job — an
        engine driven standalone with an expired deadline will hit
        ``run()``'s max_steps loudly rather than silently dropping the
        request."""
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if sample_offset < 0:
            raise ValueError("sample_offset must be >= 0")
        total = len(prompt) + max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(f"prompt+max_new_tokens={total} exceeds "
                             f"max_seq_len={self.max_seq_len}")
        if self.cache_quant == "int8" and len(prompt) > self.T:
            # dynamic per-sequence scales are frozen by the (one-shot)
            # prefill — chunked prefills would quantize chunks under
            # different scales than the final dequant (the reference's
            # dynamic cache-quant mode has the same one-shot contract)
            raise ValueError(
                f"cache_quant='int8' needs the prompt ({len(prompt)} tokens) "
                f"to prefill in one step (token_budget={self.T}); raise the "
                "budget or use the unquantized cache")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(ServingRequest(
            rid, prompt, max_new_tokens, eos_token_id,
            sampling=SamplingParams.coerce(sampling),
            sample_offset=int(sample_offset),
            trace=dict(trace) if trace else None,
            deadline_t=(self._clock() + float(deadline_s)
                        if deadline_s is not None else None)))
        return rid

    def _match_cached_prefix(self, prompt: List[int]):
        """Longest run of consecutive full prompt blocks whose chain
        hashes are content-addressable in the pool ->
        ``[(block_id, hash), ...]``."""
        matched = []
        parent = None
        for i in range(len(prompt) // self.bs):
            parent = prefix_block_hash(
                parent, prompt[i * self.bs:(i + 1) * self.bs])
            b = self.blocks.lookup(parent)
            if b is None:
                break
            matched.append((b, parent))
        return matched

    def _copy_block(self, src: int, dst: int):
        """Device-side copy of one pool block across every array of every
        layer's cache (the copy-on-write fork: the writer gets a private copy, the
        shared original stays read-only for its other owners)."""
        if len(self.kinds) > 1:
            raise ValueError("a copied block is ONE kind's: " + self.cache_spec.why_not)
        if self._cow_fn is None:
            if "cow" not in self._programs:
                at = self._block_index

                def cow(caches, s, d):
                    return jax.tree_util.tree_map(
                        lambda c: c.at[at(d)].set(c[at(s)]), caches)
                # s/d are data, not static: one compiled copy program total
                self._programs["cow"] = jax.jit(cow, donate_argnums=(0,))
            self._cow_fn = self._programs["cow"]
        self.caches = self._cow_fn(
            self.caches, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))

    def _block_index(self, b):
        """Where block(s) ``b`` lie in a pool array: behind the layer axis
        of a stacked pool."""
        return (slice(None), b) if self.cache_spec.stacked else b

    def _try_admit(self):
        while self._queue and self._free_slots:
            req = self._queue[0]
            prompt = req.prompt
            need = (len(prompt) + req.max_new_tokens + self.bs - 1) // self.bs
            matched = (self._match_cached_prefix(prompt)
                       if self.prefix_cache_enabled else [])
            m = len(matched)
            # a fully-cached block-aligned prompt still needs ≥ 1 token of
            # real prefill (no compute = no logits for the first sampled
            # token): keep the whole match, but the final token re-feeds
            # into the LAST matched block — which is shared/read-only, so
            # that one block is copy-on-write-forked below
            full_match = m > 0 and m * self.bs == len(prompt)
            n_shared = m - 1 if full_match else m
            need_fresh = need - n_shared
            # pin the match first: the matched blocks may be sitting in the
            # reuse LRU, and allocating the tail could otherwise evict them
            for b, _ in matched:
                self.blocks.fork(b)
            if not self.blocks.can_allocate(need_fresh):
                self.blocks.free([b for b, _ in matched])  # unpin
                self.admission_waits[0] += 1
                break  # head-of-line waits for retirements
            # a further kind RESERVES its worst hold (serving_model.py): the
            # head waits while a pool's reservations are full, so a running
            # row is never short of a block
            holds = [self._kind_hold(k, need) for k in range(1, len(self.kinds))]
            short = [k for k, h in enumerate(holds, 1)
                     if self.kind_reserved[k] + h > self.pools[k].num_blocks]
            if short:
                self.blocks.free([b for b, _ in matched])
                self.admission_waits[short[0]] += 1
                break
            for k, h in enumerate(holds, 1):
                self.kind_reserved[k] += h
            req.kind_reserved = holds
            req.kind_blocks = [{} for _ in holds]
            self._queue.pop(0)
            fresh = self.blocks.allocate(need_fresh)
            if full_match:
                # COW fork of the last matched block: the re-fed final
                # prompt token rewrites its own KV slot (same values) in a
                # private copy, never in the shared original
                cow_src = matched[-1][0]
                self._copy_block(cow_src, fresh[0])
                self.blocks.free([cow_src])   # drop the pin on the original
                req.blocks = [b for b, _ in matched[:-1]] + fresh
            else:
                req.blocks = [b for b, _ in matched] + fresh
            req.prefill_pos = min(m * self.bs, len(prompt) - 1)
            req.cached_prefix_tokens = req.prefill_pos
            if self.prefix_cache_enabled:
                self.prefix_hit_blocks += m
                self.prefix_miss_blocks += len(prompt) // self.bs - m
            req.slot = self._free_slots.pop()
            row = np.full((self.P,), -1, np.int32)
            row[:need] = req.blocks
            self.block_tables[req.slot] = row
            self._active[req.rid] = req

    def _publish_prefix(self, req: ServingRequest):
        """Make the request's full KV blocks content-addressable before
        they are freed, so the next request sharing the token prefix skips
        their prefill.  Only positions whose KV is actually WRITTEN count:
        the newest sampled token is fed (and cached) one step later, so it
        is excluded."""
        toks = req.prompt[:req.prefill_pos] + req.generated
        if req.generated:
            toks = toks[:-1]
        parent = None
        for i in range(len(toks) // self.bs):
            parent = prefix_block_hash(
                parent, toks[i * self.bs:(i + 1) * self.bs])
            self.blocks.publish(req.blocks[i], parent)

    def _release(self, req: ServingRequest):
        """Return a running request's blocks and batch slot to the pools
        (shared by retirement and mid-flight eviction).  With the prefix
        cache on, full blocks are published first: ``free`` then parks
        them reusable in the LRU instead of hard-freeing.  Idempotent:
        a deadline-frozen row is released at megastep harvest (ISSUE 19
        satellite) while staying in ``_active`` for the control plane's
        typed shed, so the later ``evict``/retire re-releases it."""
        if req.slot < 0:
            return
        if self.prefix_cache_enabled and req.blocks:
            self._publish_prefix(req)
        self.blocks.free(req.blocks)
        req.blocks = []
        for k, held in enumerate(req.kind_blocks, 1):
            self.pools[k].free(list(held.values()))
            self.kind_reserved[k] -= req.kind_reserved[k - 1]
        req.kind_blocks, req.kind_reserved = [], []
        for table in self.kind_tables:
            table[req.slot] = -1
        self._free_slots.append(req.slot)
        req.slot = -1

    def _first_col(self, req: ServingRequest, k: int) -> int:
        """The table column of kind ``k`` that holds the first key ``req``'s
        next query attends: 0 for a kind without a window."""
        w = self.kinds[k].window
        return 0 if w is None else max(req.cached_len - w + 1, 0) // self.bs

    def _take_blocks(self, req: ServingRequest, reach: int):
        """Before a launch that may write ``req``'s positions up to ``reach``
        (exclusive): in every further kind of cache layer, the blocks from
        the one that holds the first key its next query attends up to the
        reach's, those it does not hold yet.  Its reservation covers them."""
        if not req.kind_blocks:
            return
        reach = min(reach, len(req.prompt) + req.max_new_tokens)
        for k, held in enumerate(req.kind_blocks, 1):
            cols = [c for c in range(self._first_col(req, k), (reach - 1) // self.bs + 1)
                    if c not in held]
            if cols:
                got = self.pools[k].allocate(len(cols))
                held.update(zip(cols, got))
                self.kind_tables[k][req.slot, cols] = got

    def _give_back(self, reqs: Sequence[ServingRequest]):
        """After a launch's harvest: every block of a windowed kind that lies
        wholly under the first key a row's next query attends goes back to its
        pool (refcounted: a shared block outlives one owner), and its table
        entry reads as no block."""
        for req in reqs:
            if req.slot < 0 or not req.kind_blocks:
                continue
            for k, held in enumerate(req.kind_blocks, 1):
                lo = self._first_col(req, k)
                cols = [c for c in held if c < lo]
                if cols:
                    self.pools[k].free([held.pop(c) for c in cols])
                    self.kind_tables[k][req.slot, cols] = -1
                    self.window_blocks_released += len(cols)

    def _harvest_phase(self, counted: Dict[str, int]) -> _Phase:
        """``engine.harvest`` with the launch's counts; a spec of several kinds
        adds the engine's totals so far of blocks given back behind a window
        (monotone) and the blocks its rows hold there now."""
        if len(self.kinds) > 1:
            counted = dict(counted, window_blocks_released=self.window_blocks_released,
                           window_blocks_held=sum(
                               len(h) for r in self._active.values() for h in r.kind_blocks))
        return self._phase("harvest", **counted)

    def _retire(self, req: ServingRequest):
        req.done = True
        self._release(req)
        del self._active[req.rid]
        self._finished[req.rid] = list(req.generated)

    def evict(self, rid: int) -> ServingRequest:
        """Remove a queued or running request mid-flight (recompute
        preemption / cancellation hook for the control plane).

        Frees the request's blocks and batch slot immediately and returns
        the request object — ``prompt`` and ``generated`` are intact, so
        the caller can re-queue it with ``prompt + generated`` as the new
        prefill and get the identical greedy continuation.  ``prefill_pos``
        is reset; with the prefix cache on, the evicted request's full KV
        blocks are published before release, so a resume finds its own
        prefix cached and the recompute is nearly free (only the partial
        tail block and anything evicted under pool pressure re-prefills)."""
        req = self._active.get(rid)
        if req is not None:
            del self._active[rid]
            self._release(req)
            req.prefill_pos = 0
            return req
        for i, q in enumerate(self._queue):
            if q.rid == rid:
                return self._queue.pop(i)
        raise KeyError(f"no queued or active request with rid={rid}")

    def state_summary(self) -> Dict:
        """Host-side scheduling state, cheap and device-sync-free — the ONE
        probe shared by the fleet layer's heartbeat, the remote-replica
        state mirror, and the autoscaler (inference/fleet.py), so health
        checking and scaling decisions read the same numbers."""
        # every pool counts: a further kind's free blocks are those no admitted
        # row has reserved
        nb = sum(m.num_blocks for m in self.pools)
        free = self.blocks.num_free + sum(
            m.num_blocks - r for m, r in zip(self.pools[1:], self.kind_reserved[1:]))
        held = [sum(len(r.blocks) for r in self._active.values())] + [
            sum(len(r.kind_blocks[k]) for r in self._active.values() if r.kind_blocks)
            for k in range(len(self.kinds) - 1)]
        return {
            "queued": [(q.rid, len(q.prompt), q.max_new_tokens)
                       for q in self._queue],
            "active": {rid: len(r.blocks) + sum(len(h) for h in r.kind_blocks)
                       for rid, r in self._active.items()},
            "free_slots": len(self._free_slots),
            "blocks_free": free,
            "blocks_total": nb,
            # a pool a kind of cache layer (serving_model.py), each its own:
            # ``blocks_held`` by running rows now, ``blocks_reserved`` by their
            # admission (a windowed kind's worst hold), ``admission_waits`` the
            # times the queue's head waited on this pool (monotone)
            "pools": [{"kind": kind.name, "layers": kind.layers, "window": kind.window,
                       "blocks_total": m.num_blocks, "blocks_free": m.num_free,
                       "blocks_held": h, "blocks_reserved": r if k else h,
                       "admission_waits": waits}
                      for k, (kind, m, h, r, waits) in enumerate(zip(
                          self.kinds, self.pools, held, self.kind_reserved,
                          self.admission_waits))],
            # blocks given back behind a window while their rows ran (monotone)
            "window_blocks_released": self.window_blocks_released,
            "queue_depth": len(self._queue),
            "num_active": len(self._active),
            "pool_utilization": (1.0 - free / nb) if nb else 0.0,
            # weight-swap attribution (ISSUE 18): the fleet mirror and
            # tenant routing read these off the same state reply
            "weights_version": self.weights_version,
            "model_id": self.model_id,
            # prefix-cache summary: the hash list is bounded by the pool
            # size (tens of entries), cheap enough to piggyback on every
            # RPC reply — the frontend's prefix-affinity routing matches
            # prompt hashes against it without an extra round trip
            "prefix_cache": {
                "enabled": self.prefix_cache_enabled,
                "hashes": sorted(self.blocks.cached_hashes())
                if self.prefix_cache_enabled else [],
                "cached_blocks": self.blocks.num_cached,
                "hit_blocks": self.prefix_hit_blocks,
                "miss_blocks": self.prefix_miss_blocks,
                "evictions": self.blocks.evictions,
            },
            # megastep decode counters (monotone; workers fold the deltas
            # into their registries, the frontend folds for in-process
            # engines) + the configured K for observability
            "megastep": {
                "k": self.megastep_k,
                "megasteps": self.megasteps,
                "tokens": self.megastep_tokens,
                "mixed": self.megasteps_mixed,
                "prefill_chunks": self.prefill_chunks,
            },
            # expert layers of a sparse model (monotone; zero for a dense one)
            "moe": {
                "tokens": self.moe_tokens,
                "local_picks": self.moe_local_picks,
                "rows_grouped": self.expert_rows_grouped,
            },
            # the expert layers' tile loop, where a trunk counts it (monotone)
            "experts": {
                "touched": self.experts_touched,
                "tiles": self.expert_tiles,
                "tile_rows": self.expert_tile_rows,
                "tile_rows_live": self.expert_tile_rows_live,
            },
            # state a slot (monotone ``rows_fed``; no arrays for a model without)
            "slot_state": {
                "arrays": [name for name, _, _ in self.cache_spec.slot_state],
                "rows_fed": self.conv_rows_fed,
            },
            # paged attention (monotone; a latent cache under a learned
            # selection counts ``positions_live`` alone, another latent cache none)
            "attention": {
                "positions_live": self.attn_positions_live,
                "positions_read": self.attn_positions_read,
                "rows_kernel": self.attn_rows_kernel,
                "chunks_kernel": self.attn_chunks_kernel,
                "kv_write_tokens": self.kv_write_tokens,
                "kv_write_blocks": self.kv_write_blocks,
            },
            # the same of ONE layer of each kind, by name
            # (``attn_positions_read.window``), and ``window_positions_spared``
            # (monotone; {} for a spec of one kind)
            "attention_by_kind": dict(self.kind_counts),
            # a latent cache's rows that attended in the ``latent_rows`` kernel
            # (monotone; zero on the XLA loops and for another cache)
            "latent_attention": {
                "rows_kernel": self.latent_rows_kernel,
                "chunks_kernel": self.latent_chunks_kernel,
            },
            # learned sparse attention (monotone; zero for a model without
            # an indexer): ONE layer's counts, see ``dsa_queries`` above
            "sparse_attention": {
                "queries": self.dsa_queries,
                "positions_scored": self.dsa_positions_scored,
                "positions_selected": self.dsa_positions_selected,
                "positions_read": self.dsa_positions_read,
            },
            # a looped model (monotone; zero for a model of one pass)
            "loop": {
                "passes": self.cache_spec.passes,
                "tokens": self.loop_tokens,
                "token_passes": self.loop_token_passes,
            },
            # speculative-decode counters (ISSUE 19; same monotone
            # delta-fold contract as the megastep block above)
            "spec": {
                "k": self.spec_k,
                "accepted": self.spec_accepted_tokens,
                "drafted": self.spec_draft_tokens,
                "verify_forwards": self.spec_verify_forwards,
            },
            # cumulative host seconds per step phase — megastep cost
            # attribution without a profiler (ISSUE 15 satellite)
            "phase_seconds": dict(self.phase_seconds),
            # a launch's crossings of the host-device boundary (monotone):
            # 1 control array up and 1 read down a launch, one more read
            # where a scheduled row asked for log-probabilities
            "launch": {
                "launches": self.launches,
                "control_arrays_up": self.control_arrays_up,
                "result_reads": self.result_reads,
            },
            # where this engine's start-up went (profiler.setup_report() has
            # the process's): the rows of its ``engine.init`` spans and of
            # the ``program.acquire`` of every program its launches compiled
            # or read; grows only while programs are new
            "setup": {
                "stages": [s.row for s in self._setup_spans],
                "programs": list(self._acquired),
            },
        }

    def pop_trace_events(self) -> List[Dict]:
        """Drain span events recorded by this engine's flight recorder
        since the last call (empty when tracing is off).  In-process
        frontends drain this directly; a worker host drains it into the
        ``_w_step`` reply so the frontend can graft engine-side spans
        (prefill done, megastep boundaries) onto the fleet-wide tree."""
        if self.trace_recorder is None:
            return []
        return self.trace_recorder.drain()

    def pop_finished(self) -> Dict[int, List[int]]:
        """Drain and return requests retired since the last call,
        {rid: generated tokens}.  The control plane harvests completions
        with this between ``step()`` calls; note it drains the same record
        ``run()`` returns, so mix the two styles per-engine, not both."""
        out = self._finished
        self._finished = {}
        return out

    def pop_token_logprobs(self) -> Dict[int, List[float]]:
        """Drain per-token logprobs recorded since the last call for
        requests with ``SamplingParams.logprobs=True`` — aligned 1:1 with
        the token lists ``step()`` emitted over the same window.  The
        control plane harvests this next to the emitted tokens; greedy
        default requests never appear here."""
        out = self._emitted_logprobs
        self._emitted_logprobs = {}
        return out

    def pop_sample_probs(self) -> Dict[int, List[np.ndarray]]:
        """Drain the renormalized post-top-k/top-p distributions each
        emitted token was drawn from (``capture_sample_probs=True``
        engines only) — {rid: [float32 [V], ...]} aligned 1:1 with the
        token lists ``step()`` emitted over the same window; greedy rows
        report a one-hot at the argmax.  This is the q(x) a speculative-
        decode verifier scores draft tokens against (ROADMAP item 2);
        harvested exactly like ``pop_token_logprobs``.  NB a
        ``ServingFrontend`` driving this engine drains (and discards)
        the buffer every step — it has no per-token consumer for [V]
        arrays and must not leak them — so verifiers harvest by driving
        the engine directly."""
        out = self._emitted_sample_probs
        self._emitted_sample_probs = {}
        return out

    def reap_orphans(self) -> int:
        """Evict EVERY queued and active request and drop any unharvested
        finished/logprob state; returns how many sequences were reaped.

        The crash-recovery hook (ISSUE 11): a restarted frontend
        reattaching to a still-live engine/worker must not leave the dead
        frontend's sequences decoding unobserved forever — recovery reaps
        them and re-admits from the journal (with the prefix cache on,
        the reaped requests' full blocks were published on eviction, so
        the re-prefill largely hits cache)."""
        rids = [q.rid for q in self._queue] + list(self._active)
        for rid in rids:
            self.evict(rid)
        self._finished.clear()
        self._emitted_logprobs.clear()
        self._emitted_sample_probs.clear()
        return len(rids)

    @staticmethod
    def _fill_sampling(req: ServingRequest, slot: int, temps, top_ks,
                       top_ps, seeds, spos):
        """Marshal one request's sampling params into the per-slot host
        arrays — the ONE fill both the single-step and megastep paths
        use, so a new knob cannot reach one program and not the other."""
        sp = req.sampling
        temps[slot] = sp.temperature
        top_ks[slot] = sp.top_k
        top_ps[slot] = sp.top_p
        seeds[slot] = sp.seed
        spos[slot] = req.sample_offset + len(req.generated)

    def _phase(self, name: str, **attrs) -> _Phase:
        """``with self._phase("schedule"):`` — the one place a step's
        phases are measured (span and ``phase_seconds`` together)."""
        return _Phase(self, name, attrs)

    def _add_counts(self, got: Dict[str, int]) -> Dict[str, int]:
        """What the model's trunk counted in one launch (read out of the
        launch's result block), added to the engine's counters of the same
        names; returned for the launch's ``engine.harvest`` span."""
        for name, n in got.items():
            if "." in name or not hasattr(self, name):
                self.kind_counts[name] = self.kind_counts.get(name, 0) + n
            else:
                setattr(self, name, getattr(self, name) + n)
        return got

    def _program(self, kind: str):
        """The compiled program of ``kind``, built at its first launch
        (``step`` with the engine)."""
        attr = f"_{kind}_fn"
        if getattr(self, attr) is None:
            if kind not in self._programs:
                self._programs[kind] = getattr(self, _BUILDERS[kind])()
            setattr(self, attr, self._programs[kind])
        return getattr(self, attr)

    def _launch(self, kind: str, k: int, block: np.ndarray,
                reqs: Sequence[ServingRequest], static: Dict, **attrs):
        """One launch, for every kind: the control ``block`` goes up as ONE
        array with the dispatch; the copies of what the host will read (the
        result block; the log-probabilities only if a scheduled row asked
        for them; the sampling distributions where captured) start right
        behind it, so ``engine.wait`` finds them on their way and makes one
        blocking read.  -> (rows of the result block, logprobs or None,
        probs or None, the trunk's counts, seconds of launch + wait, whether
        the launch compiled)."""
        want_lps = any(r.sampling.logprobs for r in reqs)
        with self._launch_phase(kind, k, arrays_up=1, bytes_up=block.nbytes,
                                **attrs) as launch:
            fn = self._program(kind)
            had = fn._cache_size() if hasattr(fn, "_cache_size") else None
            caches, new_scales, res, lps, probs = fn(
                self._weights, self.program_caches(), self._rope, block,
                self.cache_scales, **static)
            n_pool = len(self.caches)
            self.caches, self.slot_state = tuple(caches[:n_pool]), tuple(caches[n_pool:])
            if self.cache_scales is not None:
                self.cache_scales = new_scales
            reads = ([res.words] + ([lps] if want_lps else [])
                     + ([probs] if probs is not None else []))
            for a in reads:
                a.copy_to_host_async()
            compiled = had is not None and fn._cache_size() > had
        if compiled:
            self._acquire(fn, launch)
        self.control_arrays_up += 1
        self.result_reads += len(reads)
        with self._phase("wait", reads=len(reads)) as wait:
            out, counts = res.read()
            lps = np.asarray(lps) if want_lps else None
            probs = np.asarray(probs) if probs is not None else None
            counted = self._add_counts(counts)
        return out, lps, probs, counted, launch.seconds + wait.seconds, compiled

    def _launch_phase(self, kind: str, k: int, **attrs) -> _Phase:
        """``engine.launch`` of one compiled program: ``k`` iterations of
        ``kind``; ``launch`` counts launches (the ``FlightRecorder``'s
        ``megastep`` events carry it too) and ``t_mono`` is this engine's
        clock, so that a recorder event's ``t`` can be placed on the trace.
        ``passes``: how often an iteration runs the model's layers (1 but for
        a looped model).  ``_launch`` adds ``arrays_up`` and ``bytes_up``, the
        control arrays sent up with the dispatch; a mixed launch
        ``prefill_rows``, the rows it feeds chunks."""
        self.launches += 1
        return self._phase("launch", kind=kind, k=k, launch=self.launches,
                           t_mono=self._clock(), passes=self.cache_spec.passes,
                           **attrs)

    def _acquire(self, fn, launch: _Phase):
        """``program.acquire``, after the fact: ``launch`` has just ended and
        found ``fn``'s cache grown, so its seconds held the trace, the
        lowering and the compile or cache read of one more program."""
        row = SETUP.acquired(fn.__name__, launch.seconds, kind=launch.attrs["kind"],
                             k=launch.attrs["k"])
        self._acquired.append(row)

    def step(self) -> Dict[int, List[int]]:
        """One engine iteration: schedule -> compiled step(s) -> retire.
        Returns tokens appended this step, {rid: [tok, ...]}.

        ARMING: whenever any scheduled row is decoding (and
        ``megastep_k > 1``), up to ``megastep_k`` iterations run inside
        ONE compiled ``lax.scan`` — the pure-decode scan when every row
        is decoding (int8 included; its scales ride the carry), the
        MIXED scan when prefilling rows share the batch (each iteration
        feeds those rows one block-size prompt chunk as data).  A mixed
        launch divides its ``token_budget`` by CHUNK: one token to each
        decoding row, then one chunk to each prefilling row in admission
        order while chunks fit, so every waiting prompt that fits rides
        the scan, not the first alone (``_route``).  The
        returned lists then carry up to K tokens per request and the
        host — admission included — only observes the engine at megastep
        boundaries.  Prefill-only batches (plus int8 one-shot prefill
        and ``megastep_k=1``) run the single-step program.

        A launch crosses the host-device boundary once each way
        (``_launch``, launch_block.py): its control rows go up as ONE packed
        ``int32`` block with the dispatch, and what the host reads of it
        (tokens, masks, the trunk's counts) comes down as ONE, its copy
        started at the dispatch; log-probabilities are a second read, made
        only when a scheduled row asked for them
        (``state_summary()["launch"]`` counts both).

        Spans (``RecordEvent``, children of ``engine.step``):
        ``engine.admit``, ``engine.schedule``, ``engine.launch``,
        ``engine.wait``, ``engine.harvest``."""
        with RecordEvent("engine.step"):
            with self._phase("admit"):
                self._try_admit()
            if not self._active:
                return {}
            if self._faults is not None:
                from .faults import prompt_signature

                # detail carries each active request's prompt signature so a
                # poison spec (match="p<t0>-<t1>-...") fires exactly when its
                # request is scheduled — and keeps firing on whichever replica
                # the request is retried on (the resumed prefill keeps the
                # original prompt as its head)
                self._faults.fire(
                    "engine.step",
                    detail=" ".join(prompt_signature(r.prompt)
                                    for r in self._active.values()))
            with self._phase("schedule"):
                sched, launch = self._route()
            if launch is not None:
                return launch()
            if not sched:
                return {}
            return self._single_step(sched)

    def _route(self):
        """Pick this step's rows and program: ``(sched, launch)`` where
        ``sched`` is [(req, n_tokens, finishes_prefill)] and ``launch``
        runs the armed scan or verify program (None: the single-step
        program takes ``sched``).

        Two bookings of the ``token_budget``.  A launch that will be the
        MIXED scan (a row decodes, a row prefills, ``megastep_k > 1``,
        cache not int8, a chunk fits the buffer) gives each decoding row
        one token and then DIVIDES the rest among the prefilling rows,
        ONE CHUNK EACH (``min(block_size, prompt left)``, the most a row
        packs into any one iteration), in admission order, until the next
        row's chunk no longer fits: the scan feeds a row one chunk an
        iteration whether one row prefills or ten, so a waiting prompt
        costs the rows ahead of it nothing.  Every other launch books for
        the single-step program: a prefilling row takes
        ``min(prompt left, budget)`` whole, first come first served."""
        # rows with slot < 0 are deadline-frozen and already released at a
        # megastep harvest — they stay in _active only until the control
        # plane finalizes the typed shed, and must never re-schedule
        rows = [r for r in self._active.values() if r.slot >= 0]
        # decode first (latency), then fill with prefill chunks
        dec_rows = [r for r in rows if not r.in_prefill][:self.T]
        waiting = [r for r in rows if r.in_prefill]
        budget = self.T - len(dec_rows)
        # MIXED-PHASE arming (ISSUE 16): any decoding row + any prefilling
        # row -> run both phases inside one scan instead of falling back
        # to per-token host stepping.  int8 keeps one-shot prefill
        # (dynamic scales freeze at prefill, chunking would violate it);
        # bs > T cannot exact-pack a full chunk into the token buffer.
        if (dec_rows and waiting and self.megastep_k > 1
                and self.cache_quant != "int8" and self.pc <= self.T):
            pre_rows = []
            left = budget
            for r in waiting:
                # worst-case packed tokens this row adds to any one
                # iteration: its first chunk (chunks only shrink)
                cost = min(self.pc, len(r.prompt) - r.prefill_pos)
                if cost > left:
                    break   # admission order: nobody overtakes this row
                pre_rows.append(r)
                left -= cost
            if pre_rows:
                return [], partial(self._megastep_mixed, dec_rows, pre_rows)
        sched = [(r, 1, False) for r in dec_rows]
        for req in waiting:
            if budget <= 0:
                break
            need = len(req.prompt) - req.prefill_pos
            if self.cache_quant == "int8" and need > budget:
                # int8 dynamic scales freeze at prefill: the prefill must
                # land in ONE step, so wait for enough budget (bounded
                # wait — decoding slots retire and free it)
                continue
            n = min(need, budget)
            sched.append((req, n, req.prefill_pos + n >= len(req.prompt)))
            budget -= n
            if self._faults is not None:
                from .faults import prompt_signature

                # chunk-boundary failpoint, single-step path: fires
                # before any device mutation, once per prompt chunk
                self._faults.fire("engine.prefill_chunk",
                                  detail=prompt_signature(req.prompt))
        if not sched:
            return sched, None
        # pure-decode steps run the tight [B]-token program (mq=1); steps
        # carrying prefill chunks run the [T]-token program (mq=T)
        decode_only = len(sched) == len(dec_rows)
        # SPECULATIVE arming (ISSUE 19): pure-decode batches on a
        # spec_k > 0 engine try n-gram drafting first; one verify
        # forward then commits accepted+1 tokens per row.  int8 is
        # excluded (speculative rewind would need scale rewind), and a
        # launch with NO non-empty draft falls through — the megastep
        # is strictly better when there is nothing to verify.
        if (decode_only and self.spec_k > 0 and self.cache_quant != "int8"
                and any(r.sampling.spec for r in dec_rows)):
            drafts = self._draft(dec_rows)
            if any(drafts.values()):
                armed = True
                if self._faults is not None:
                    from .faults import prompt_signature
                    try:
                        self._faults.fire(
                            SPEC_VERIFY,
                            detail=" ".join(prompt_signature(r.prompt)
                                            for r in dec_rows))
                    except Exception:
                        # degrade contract: a verify fault falls this
                        # step back to the non-spec megastep/single-step
                        # path — token-identical, never a wrong token
                        armed = False
                if armed:
                    return sched, partial(self._spec_step, dec_rows, drafts)
        if (decode_only and self.megastep_k > 1
                and max(r.max_new_tokens - len(r.generated)
                        for r in dec_rows) > 1):
            return sched, partial(self._megastep, dec_rows)
        return sched, None

    def _single_step(self, sched: List[tuple]) -> Dict[int, List[int]]:
        """Run ``sched`` through the single-step program: prefill-only
        batches, int8 one-shot prefill, ``megastep_k=1``."""
        with self._phase("schedule"):
            enc = np.zeros((self.B,), np.int32)
            dec = np.zeros((self.B,), np.int32)
            now = np.zeros((self.B,), np.int32)
            # pure-decode steps run the tight [B]-token program (mq=1);
            # steps carrying prefill chunks run the [T]-token program (mq=T)
            decode_only = all(not r.in_prefill for r, _, _ in sched)
            tokens = np.zeros((self.B if decode_only else self.T,), np.int32)
            # stable slot order so cu_seqlens is monotone over batch rows
            sched.sort(key=lambda s: s[0].slot)
            cu = np.zeros((self.B + 1,), np.int32)
            temps = np.zeros((self.B,), np.float32)
            top_ks = np.zeros((self.B,), np.int32)
            top_ps = np.ones((self.B,), np.float32)
            seeds = np.zeros((self.B,), np.int32)
            spos = np.zeros((self.B,), np.int32)
            per_slot = {s[0].slot: s for s in sched}
            pos = 0
            for slot in range(self.B):
                cu[slot + 1] = pos
                if slot not in per_slot:
                    continue
                req, n, _ = per_slot[slot]
                self._fill_sampling(req, slot, temps, top_ks, top_ps, seeds,
                                    spos)
                if req.in_prefill:
                    chunk = req.prompt[req.prefill_pos:req.prefill_pos + n]
                    enc[slot] = n
                    dec[slot] = req.prefill_pos
                    self.prefill_tokens_computed += n
                else:
                    chunk = [req.generated[-1] if req.generated
                             else req.prompt[-1]]
                    # cached tokens = prompt + generated[:-1]; the latest sampled
                    # token is only being fed (and cached) THIS step
                    dec[slot] = req.context_len - 1
                now[slot] = n
                tokens[pos:pos + n] = chunk
                pos += n
                cu[slot + 1] = pos
                self._take_blocks(req, int(dec[slot]) + n)
            block = control_layout("step", self.B, self.P, len(tokens),
                                   len(self.kinds)).pack(dict(
                token_ids=tokens, enc=enc, dec=dec, now=now, cu=cu,
                temps=temps, top_ks=top_ks, top_ps=top_ps,
                seeds=seeds, sample_pos=spos, **self._table_rows()))

        out, lps, probs, counted, _, _ = self._launch(
            "step", 1, block, [s[0] for s in sched],
            {"mq": 1 if decode_only else self.T})
        nxt = out["toks"]
        with self._harvest_phase(counted):
            emitted: Dict[int, List[int]] = {}
            for req, n, finishes in sched:
                if req.in_prefill:
                    req.prefill_pos += n
                    req.chunks_fed += 1
                    self.prefill_chunks += 1
                    if self.trace_recorder is not None and req.trace is not None:
                        self.trace_recorder.record(
                            req.trace["trace"], req.trace["span"],
                            req.trace.get("parent"), "prefill_chunk",
                            rid=req.trace.get("rid"),
                            chunk=req.chunks_fed - 1, tokens=n)
                    if not finishes:
                        continue  # mid-prompt chunk: sampled token is meaningless
                    if self.trace_recorder is not None and req.trace is not None:
                        self.trace_recorder.record(
                            req.trace["trace"], req.trace["span"],
                            req.trace.get("parent"), "prefill",
                            rid=req.trace.get("rid"),
                            prompt_len=len(req.prompt))
                tok = int(nxt[req.slot])
                req.generated.append(tok)
                if req.sampling.logprobs:
                    req.logprob_values.append(float(lps[req.slot]))
                    self._emitted_logprobs.setdefault(req.rid, []).append(
                        float(lps[req.slot]))
                if probs is not None:
                    # .copy(): probs[slot] is a view pinning the whole [B,V]
                    # step array alive (the megastep path's fancy-indexing
                    # already copies)
                    self._emitted_sample_probs.setdefault(req.rid, []).append(
                        probs[req.slot].copy())
                emitted.setdefault(req.rid, []).append(tok)
                hit_eos = (req.eos_token_id is not None and tok == req.eos_token_id)
                if hit_eos or len(req.generated) >= req.max_new_tokens:
                    self._retire(req)
            self._give_back([s[0] for s in sched])
        return emitted

    def _deadline_budgets(self, by_slot: Dict[int, "ServingRequest"]
                          ) -> np.ndarray:
        """Per-slot deadline budgets in SCAN ITERATIONS, computed on the
        host at megastep launch so the compiled body checks deadlines as
        pure data (wall clock never enters a traced program).  A row with
        no deadline — or no per-iteration time estimate yet — gets an
        effectively infinite budget; ``floor((deadline_t - now) / tau)``
        otherwise, so a conservative (large) tau freezes EARLY: that
        costs throughput, never correctness, and overshoot past the
        deadline stays zero."""
        dl = np.full((self.B,), 2 ** 30, np.int32)
        tau = self._tau
        if tau is None or tau <= 0:
            return dl
        now = self._clock()
        for slot, req in by_slot.items():
            if req.deadline_t is not None:
                dl[slot] = max(0, int((req.deadline_t - now) / tau))
        return dl

    def _update_tau(self, execute_s: float, k: int, compiled: bool):
        """Fold one megastep's measured execute time into the EWMA
        per-iteration estimate (skipped when deadline_token_seconds was
        injected, and on compile launches — trace+compile time is not
        steady-state iteration cost)."""
        if self._tau_override or compiled or k <= 0 or execute_s <= 0:
            return
        x = execute_s / k
        self._tau = x if self._tau is None else 0.8 * self._tau + 0.2 * x

    def _free_frozen(self, reqs: List[ServingRequest], dl: np.ndarray,
                     k: int):
        """ISSUE 19 satellite (the r16 remain): a row whose in-graph
        deadline budget ran out inside this scan is FROZEN — it will
        never emit again, but it used to park its slot and blocks until
        the control plane's typed shed at some later boundary.  Free
        them at harvest instead: the request stays in ``_active`` (slot
        -1, never re-scheduled) so the DEADLINE_EXCEEDED shed still
        happens at the control plane, while the queue head admits into
        the freed slot THIS control step.  A launch budget ``dl <= k``
        means the scan drove it to 0; ``_release`` is idempotent, so
        the shed's ``evict`` re-release is safe."""
        freed = False
        for req in reqs:
            if not req.done and req.slot >= 0 and dl[req.slot] <= k:
                self._release(req)
                freed = True
        if freed:
            self._try_admit()

    def _draft(self, reqs: List[ServingRequest]) -> Dict[int, List[int]]:
        """Host-side n-gram drafts for one spec launch, {rid: [tok, ..]}.
        Per request: drafting reads ONLY its own ``prompt + generated``
        history, and the length is capped at ``min(spec_k, remaining-1)``
        so (a) speculative KV writes stay inside the allocated blocks
        and (b) a full accept commits at most ``remaining`` tokens — no
        budget overshoot to truncate.  A ``engine.spec_draft`` fault
        degrades that ROW to an empty draft: it rides the verify and
        commits exactly its one non-spec token."""
        drafts: Dict[int, List[int]] = {}
        for r in reqs:
            d: List[int] = []
            cap = min(self.spec_k, r.max_new_tokens - len(r.generated) - 1)
            if r.sampling.spec and cap > 0:
                try:
                    if self._faults is not None:
                        from .faults import prompt_signature
                        self._faults.fire(SPEC_DRAFT,
                                          detail=prompt_signature(r.prompt))
                    d = ngram_draft(r.prompt + r.generated, cap)
                except Exception:
                    d = []   # degrade: this row rides undrafted
            drafts[r.rid] = d
        return drafts

    def _spec_step(self, reqs: List[ServingRequest],
                   drafts: Dict[int, List[int]]) -> Dict[int, List[int]]:
        """ONE batched verify forward over ``[last_token] + draft`` per
        row: the compiled program (``_build_spec_verify``) redraws every
        position with the exact non-spec key stream and reports the
        accepted draft-prefix length; the host commits the redraw
        matrix's first ``accepted + 1`` columns (the redraw IS the
        committed token at every accepted position — see the program's
        docstring), truncating at EOS exactly like the non-spec harvest.
        Counters: ``spec_verify_forwards`` counts ROWS scored (a
        per-token forward-equivalent, so forwards ÷ committed tokens is
        exactly 1.0 when nothing accepts and < 1.0 iff speculation
        pays), ``spec_draft_tokens`` counts proposals,
        ``spec_accepted_tokens`` counts committed draft tokens."""
        with self._phase("schedule"):
            B, sk = self.B, self.spec_k
            Kp1 = sk + 1
            tokens = np.zeros((B * Kp1,), np.int32)
            dec = np.zeros((B,), np.int32)
            now = np.zeros((B,), np.int32)
            cu = np.zeros((B + 1,), np.int32)
            dlen = np.zeros((B,), np.int32)
            draft_a = np.zeros((B, sk), np.int32)
            temps = np.zeros((B,), np.float32)
            top_ks = np.zeros((B,), np.int32)
            top_ps = np.ones((B,), np.float32)
            seeds = np.zeros((B,), np.int32)
            spos = np.zeros((B,), np.int32)
            reqs = sorted(reqs, key=lambda r: r.slot)
            by_slot = {r.slot: r for r in reqs}
            pos = 0
            for slot in range(B):
                cu[slot + 1] = pos
                req = by_slot.get(slot)
                if req is None:
                    continue
                d = drafts.get(req.rid, [])
                row = [req.generated[-1] if req.generated else req.prompt[-1]]
                row.extend(int(t) for t in d)
                tokens[pos:pos + len(row)] = row
                dec[slot] = req.context_len - 1
                now[slot] = len(row)
                dlen[slot] = len(d)
                draft_a[slot, :len(d)] = d
                self._fill_sampling(req, slot, temps, top_ks, top_ps, seeds,
                                    spos)
                pos += len(row)
                cu[slot + 1] = pos
            block = control_layout("spec", B, self.P, sk, len(self.kinds)).pack(dict(
                token_ids=tokens, dec=dec, now=now, cu=cu,
                dlen=dlen, draft=draft_a, temps=temps, top_ks=top_ks,
                top_ps=top_ps, seeds=seeds, sample_pos=spos, **self._table_rows()))
        out, lps, probs, counted, _, _ = self._launch("spec", Kp1, block, reqs, {})
        nxt = out["toks"]       # [B, spec_k+1] redraws
        acc = out["acc"]        # [B] accepted draft-prefix lengths

        with self._harvest_phase(counted):
            emitted: Dict[int, List[int]] = {}
            for req in reqs:
                s = req.slot
                new = [int(t) for t in nxt[s, :int(acc[s]) + 1]]
                if req.eos_token_id is not None and req.eos_token_id in new:
                    # the non-spec engine stops AT the EOS: accepted draft
                    # tokens past it were never going to be generated
                    new = new[:new.index(req.eos_token_id) + 1]
                d = int(dlen[s])
                req.generated.extend(new)
                if req.sampling.logprobs:
                    row_lps = [float(v) for v in lps[s, :len(new)]]
                    req.logprob_values.extend(row_lps)
                    self._emitted_logprobs.setdefault(req.rid, []).extend(
                        row_lps)
                if probs is not None:
                    self._emitted_sample_probs.setdefault(req.rid, []).extend(
                        probs[s, j].copy() for j in range(len(new)))
                emitted[req.rid] = new
                self.spec_verify_forwards += 1
                self.spec_draft_tokens += d
                self.spec_accepted_tokens += len(new) - 1
                if self.trace_recorder is not None and req.trace is not None:
                    self.trace_recorder.record(
                        req.trace["trace"], req.trace["span"],
                        req.trace.get("parent"), "spec_verify",
                        rid=req.trace.get("rid"), drafted=d,
                        accepted=len(new) - 1, tokens=len(new))
                hit_eos = (req.eos_token_id is not None
                           and new[-1] == req.eos_token_id)
                if hit_eos or len(req.generated) >= req.max_new_tokens:
                    self._retire(req)
        return emitted

    def _megastep(self, reqs: List[ServingRequest]) -> Dict[int, List[int]]:
        """Run up to ``megastep_k`` decode iterations in one compiled
        scan over the scheduled (all-decoding) requests.  K rounds up to
        a power of two (bounded compile count: one program per distinct
        K) capped at ``megastep_k``; rows that finish inside the scan are
        masked in-graph and their trailing samples dropped here."""
        if self._faults is not None:
            from .faults import prompt_signature

            # same poison-routing contract as the engine.step site, on the
            # batched-decode path: chaos schedules arm this to cover the
            # one-RPC-per-K-tokens fleet plumbing
            self._faults.fire(
                "engine.megastep",
                detail=" ".join(prompt_signature(r.prompt) for r in reqs))
        with self._phase("schedule"):
            kmax = max(r.max_new_tokens - len(r.generated) for r in reqs)
            K = 1
            while K < min(self.megastep_k, kmax):
                K *= 2
            K = min(K, self.megastep_k)
            B = self.B
            toks = np.zeros((B,), np.int32)
            dec = np.zeros((B,), np.int32)
            now = np.zeros((B,), np.int32)
            occ_idx = np.zeros((B,), np.int32)
            cu = np.zeros((B + 1,), np.int32)
            active = np.zeros((B,), bool)
            remaining = np.zeros((B,), np.int32)
            eos = np.full((B,), -1, np.int32)
            temps = np.zeros((B,), np.float32)
            top_ks = np.zeros((B,), np.int32)
            top_ps = np.ones((B,), np.float32)
            seeds = np.zeros((B,), np.int32)
            spos = np.zeros((B,), np.int32)
            reqs = sorted(reqs, key=lambda r: r.slot)
            by_slot = {r.slot: r for r in reqs}
            pos = 0
            for slot in range(B):
                req = by_slot.get(slot)
                if req is not None:
                    occ_idx[pos] = slot
                    toks[slot] = (req.generated[-1] if req.generated
                                  else req.prompt[-1])
                    dec[slot] = req.context_len - 1
                    now[slot] = 1
                    active[slot] = True
                    remaining[slot] = req.max_new_tokens - len(req.generated)
                    if req.eos_token_id is not None:
                        eos[slot] = req.eos_token_id
                    self._fill_sampling(req, slot, temps, top_ks, top_ps,
                                        seeds, spos)
                    pos += 1
                cu[slot + 1] = pos
            dl = self._deadline_budgets(by_slot)
            for req in reqs:
                self._take_blocks(req, req.cached_len + K)
            block = control_layout("mega", B, self.P, 0, len(self.kinds)).pack(dict(
                toks=toks, dec=dec, now=now, cu=cu, occ_idx=occ_idx,
                active=active, remaining=remaining, dl=dl,
                eos=eos, temps=temps, top_ks=top_ks, top_ps=top_ps, seeds=seeds,
                sample_pos=spos, **self._table_rows()))
        out, lps_o, probs_o, counted, execute_s, compiled = self._launch(
            "mega", K, block, reqs, {"K": K})
        toks_o, valid_o = out["toks"], out["valid"]       # [K, B]
        self.megasteps += 1
        self._update_tau(execute_s, K, compiled)

        with self._harvest_phase(counted):
            emitted: Dict[int, List[int]] = {}
            for req in reqs:
                s = req.slot
                col = valid_o[:, s]
                new = [int(t) for t in toks_o[:, s][col]]
                req.generated.extend(new)
                if req.sampling.logprobs:
                    row_lps = [float(v) for v in lps_o[:, s][col]]
                    req.logprob_values.extend(row_lps)
                    self._emitted_logprobs.setdefault(req.rid, []).extend(row_lps)
                if probs_o is not None and new:
                    self._emitted_sample_probs.setdefault(req.rid, []).extend(
                        probs_o[:, s][col])   # [n_valid, V]
                emitted[req.rid] = new
                self.megastep_tokens += len(new)
                if self.trace_recorder is not None and req.trace is not None:
                    self.trace_recorder.record(
                        req.trace["trace"], req.trace["span"],
                        req.trace.get("parent"), "megastep",
                        rid=req.trace.get("rid"), tokens=len(new), k=K,
                        launch=self.launches)
                hit_eos = (req.eos_token_id is not None and new
                           and new[-1] == req.eos_token_id)
                if hit_eos or len(req.generated) >= req.max_new_tokens:
                    self._retire(req)
            self._give_back(reqs)
            self._free_frozen(reqs, dl, K)
        return emitted

    def _megastep_mixed(self, dec_reqs: List[ServingRequest],
                        pre_reqs: List[ServingRequest]
                        ) -> Dict[int, List[int]]:
        """Run up to ``megastep_k`` MIXED-PHASE iterations in one
        compiled scan: ``dec_reqs`` decode one token per iteration while
        ``pre_reqs`` consume one block-size prompt chunk per iteration
        (then decode in place once their prompt completes).  ``pre_reqs``
        are the prefilling rows ``_route`` divided the launch's token
        budget among, one chunk each in admission order, so the
        worst-case packed-token total fits the [T] buffer; the scan
        feeds each of them a chunk an iteration.  Unlike the pure-decode
        scan (power-of-two K buckets), mixed launches ALWAYS run the
        full ``megastep_k`` bucket: one compiled mixed program per
        engine.  Mixed arms under live
        admission, so a tail-sized launch (every row near completion)
        would compile a second multi-second XLA program mid-traffic —
        far costlier than the masked tail iterations it saves."""
        reqs = dec_reqs + pre_reqs
        if self._faults is not None:
            from .faults import prompt_signature

            self._faults.fire(
                "engine.megastep",
                detail=" ".join(prompt_signature(r.prompt) for r in reqs))
            for r in pre_reqs:
                # chunk-boundary failpoint, the mixed path's one site:
                # fires BEFORE the compiled call (a fault never leaves
                # half-committed tokens), once per prompt entering the
                # scan chunked
                self._faults.fire("engine.prefill_chunk",
                                  detail=prompt_signature(r.prompt))
        with self._phase("schedule"):
            C = self.pc
            K = self.megastep_k
            B = self.B
            toks = np.zeros((B,), np.int32)
            cached = np.zeros((B,), np.int32)
            pp = np.zeros((B,), np.int32)
            pp0 = np.zeros((B,), np.int32)
            plen = np.zeros((B,), np.int32)
            prompt_buf = np.zeros((B, K * C), np.int32)
            active = np.zeros((B,), bool)
            remaining = np.zeros((B,), np.int32)
            eos = np.full((B,), -1, np.int32)
            temps = np.zeros((B,), np.float32)
            top_ks = np.zeros((B,), np.int32)
            top_ps = np.ones((B,), np.float32)
            seeds = np.zeros((B,), np.int32)
            spos = np.zeros((B,), np.int32)
            by_slot = {r.slot: r for r in reqs}
            for slot, req in by_slot.items():
                active[slot] = True
                remaining[slot] = req.max_new_tokens - len(req.generated)
                if req.eos_token_id is not None:
                    eos[slot] = req.eos_token_id
                self._fill_sampling(req, slot, temps, top_ks, top_ps, seeds,
                                    spos)
                if req.in_prefill:
                    # the prompt window this scan can reach: K chunks of C
                    pp[slot] = pp0[slot] = cached[slot] = req.prefill_pos
                    plen[slot] = len(req.prompt)
                    window = req.prompt[req.prefill_pos:
                                        req.prefill_pos + K * C]
                    prompt_buf[slot, :len(window)] = window
                else:
                    toks[slot] = (req.generated[-1] if req.generated
                                  else req.prompt[-1])
                    cached[slot] = req.context_len - 1
                    # pp == plen marks the row as decoding from iteration 0
                    pp[slot] = pp0[slot] = plen[slot] = len(req.prompt)
            dl = self._deadline_budgets(by_slot)
            for req in reqs:
                self._take_blocks(req, req.cached_len + K * (C if req.in_prefill else 1))
            block = control_layout("mixed", B, self.P, K * C, len(self.kinds)).pack(dict(
                toks=toks, cached=cached, pp=pp, pp0=pp0, plen=plen,
                prompt_buf=prompt_buf, active=active,
                remaining=remaining, dl=dl, eos=eos, temps=temps, top_ks=top_ks,
                top_ps=top_ps, seeds=seeds, sample_pos=spos, **self._table_rows()))
        out, lps_o, probs_o, counted, execute_s, compiled = self._launch(
            "mixed", K, block, reqs, {"K": K}, prefill_rows=len(pre_reqs))
        toks_o, emits_o = out["toks"], out["valid"]       # [K, B]
        pp_f = out["pp"]                                  # [B] final prefill positions
        self.megasteps += 1
        self.megasteps_mixed += 1
        self._update_tau(execute_s, K, compiled)

        with self._harvest_phase(counted):
            emitted: Dict[int, List[int]] = {}
            for req in sorted(reqs, key=lambda r: r.slot):
                s = req.slot
                col = emits_o[:, s]
                new = [int(t) for t in toks_o[:, s][col]]
                fed = int(pp_f[s]) - req.prefill_pos
                if fed > 0:
                    # reconstruct the chunk boundaries the scan crossed (all
                    # full C except a completing tail) for counters + spans
                    req.prefill_pos += fed
                    self.prefill_tokens_computed += fed
                    nch = -(-fed // C)
                    for i in range(nch):
                        ntok = min(C, fed - i * C)
                        req.chunks_fed += 1
                        self.prefill_chunks += 1
                        if (self.trace_recorder is not None
                                and req.trace is not None):
                            self.trace_recorder.record(
                                req.trace["trace"], req.trace["span"],
                                req.trace.get("parent"), "prefill_chunk",
                                rid=req.trace.get("rid"),
                                chunk=req.chunks_fed - 1, tokens=ntok)
                    if (not req.in_prefill and self.trace_recorder is not None
                            and req.trace is not None):
                        self.trace_recorder.record(
                            req.trace["trace"], req.trace["span"],
                            req.trace.get("parent"), "prefill",
                            rid=req.trace.get("rid"),
                            prompt_len=len(req.prompt))
                req.generated.extend(new)
                if req.sampling.logprobs:
                    row_lps = [float(v) for v in lps_o[:, s][col]]
                    req.logprob_values.extend(row_lps)
                    self._emitted_logprobs.setdefault(req.rid, []).extend(
                        row_lps)
                if probs_o is not None and new:
                    self._emitted_sample_probs.setdefault(req.rid, []).extend(
                        probs_o[:, s][col])   # [n_valid, V]
                emitted[req.rid] = new
                self.megastep_tokens += len(new)
                if self.trace_recorder is not None and req.trace is not None:
                    self.trace_recorder.record(
                        req.trace["trace"], req.trace["span"],
                        req.trace.get("parent"), "megastep",
                        rid=req.trace.get("rid"), tokens=len(new), k=K,
                        launch=self.launches)
                hit_eos = (req.eos_token_id is not None and new
                           and new[-1] == req.eos_token_id)
                if hit_eos or len(req.generated) >= req.max_new_tokens:
                    self._retire(req)
            self._give_back(reqs)
            self._free_frozen(reqs, dl, K)
        return emitted

    def run(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Drive until every queued/active request retires.

        Raises ``RuntimeError`` when ``max_steps`` is exhausted with
        requests still queued or active — a truncated run must not be
        mistaken for completion (the returned dict would silently miss
        the unfinished requests' tokens).
        """
        for _ in range(max_steps):
            if not self._queue and not self._active:
                break
            self.step()
            if self._queue and not self._active:
                self._try_admit()  # retirements this step freed capacity
            if self._queue and not self._active:
                # nothing running, everything free, and the queue head still
                # could not be admitted: it can NEVER fit (pool/slot capacity
                # too small) — fail loudly instead of spinning no-ops
                head = self._queue[0]
                need = (len(head.prompt) + head.max_new_tokens
                        + self.bs - 1) // self.bs
                raise RuntimeError(
                    f"request {head.rid} needs {need} cache blocks but the "
                    f"pool only has {self.blocks.num_blocks} total "
                    f"({self.blocks.num_free} free with nothing running) — "
                    "raise num_blocks/max_seq_len or shrink the request")
        if self._queue or self._active:
            raise RuntimeError(
                f"ServingEngine.run: max_steps={max_steps} exhausted with "
                f"{len(self._active)} active and {len(self._queue)} queued "
                "request(s) unfinished — raise max_steps (or drain with "
                "step() and read partial results from the request objects)")
        return dict(self._finished)

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def prefix_evictions(self) -> int:
        """Cached blocks dropped from the reuse LRU under allocation
        pressure (monotone; the control plane folds it into metrics)."""
        return self.blocks.evictions

    def cached_block_hashes(self) -> Set[str]:
        """Chain hashes content-addressable in this engine's pool right
        now — what prefix-affinity routing scores a prompt against
        (``fleet.RemoteReplica`` mirrors this from ``state_summary``)."""
        if not self.prefix_cache_enabled:
            return set()
        return self.blocks.cached_hashes()

    # ------------------------------------------------- block transfer
    # (kv_fabric.py: disaggregated prefill/decode moves KV between
    # engines as bit-exact payloads keyed by chain hash)

    def _check_transferable(self, op: str):
        if not self.cache_spec.transferable or self.cache_spec.slot_state:
            raise ValueError(
                f"{op} cannot be used with {type(self).__name__} over this "
                "model: " + self.cache_spec.why_not)
        if self.cache_quant == "int8":
            raise ValueError(
                f"{op} cannot be used with cache_quant='int8': the int8 "
                "cache dequantizes through per-(slot, kv-head) DYNAMIC "
                "scales frozen at each sequence's own prefill, so a "
                "block's uint8 payload is only meaningful under its "
                "writer's scales — another engine importing it would "
                "dequantize garbage. Disaggregated transfer requires the "
                "unquantized cache")

    def export_blocks_packed(self, hashes: Sequence[str]) -> Tuple[Dict,
                                                                   bytes]:
        """Bit-exact KV payload for a chain of published block hashes
        (parent-first order) as ONE contiguous packed buffer — the
        binary data-plane form (inference/blockwire.py, ISSUE 20).
        Stops at the first hash this pool no longer holds — a chain is
        only usable up to its first gap, so exporting past one would
        ship unmatchable blocks.  Returns ``(header, raw)``: a
        self-describing geometry header (``shape`` = ``[2, layers,
        nblocks, kv_heads, block_size, head_dim]``, K/V stacked over
        the engine's native per-block cache slice) plus the raw bytes
        of one batched device→host gather — a single jitted stacked
        gather + ONE ``np.asarray`` for the whole chain, not
        ``2 × layers × nblocks`` individual copies."""
        self._check_transferable("export_blocks_packed")
        held: List[str] = []
        ids: List[int] = []
        for h in hashes:
            b = self.blocks.lookup(h)
            if b is None:
                break
            held.append(h)
            ids.append(int(b))
        header = {"block_size": self.bs, "layers": self.L,
                  "kv_heads": self.KV, "head_dim": self.D,
                  "dtype": self._cache_dtype, "hashes": held,
                  "shape": [2, self.L, len(held), self.KV, self.bs, self.D]}
        if not held:
            return header, b""
        if "gather" not in self._programs:
            stacked = self.cache_spec.stacked

            def gather(kcs, vcs, bids):
                k = kcs[:, bids] if stacked else jnp.stack([kc[bids] for kc in kcs])
                v = vcs[:, bids] if stacked else jnp.stack([vc[bids] for vc in vcs])
                return jnp.stack([k, v])   # [2, L, n, KV, bs, D]
            self._programs["gather"] = jax.jit(gather)
        packed = self._programs["gather"](self.key_caches,
                                          self.value_caches,
                                          jnp.asarray(ids, jnp.int32))
        return header, np.asarray(packed).tobytes()

    def export_blocks(self, hashes: Sequence[str]) -> Dict:
        """Bit-exact KV payload for a chain of published block hashes
        (parent-first order) in the dict form — the compatibility /
        frontend-relay fallback; ``export_blocks_packed`` is the data
        plane.  Both run the same single batched device→host gather
        (the per-block-per-layer ``np.asarray`` loop this replaced cost
        ``2 × layers × nblocks`` host round trips); the dict's arrays
        are host-side views into that one buffer."""
        header, raw = self.export_blocks_packed(hashes)
        blocks: Dict[str, Dict[str, list]] = {}
        held = header["hashes"]
        if held:
            arr = np.frombuffer(raw, dtype=_np_dtype(header["dtype"]))
            arr = arr.reshape(header["shape"])
            for i, h in enumerate(held):
                blocks[h] = {"k": [arr[0, li, i] for li in range(self.L)],
                             "v": [arr[1, li, i] for li in range(self.L)]}
        return {"block_size": self.bs, "layers": self.L, "kv_heads": self.KV,
                "head_dim": self.D, "dtype": header["dtype"],
                "blocks": blocks}

    def import_blocks(self, payload: Dict) -> int:
        """Install an ``export_blocks`` payload into this pool: allocate
        a block, write the bits on device, ``publish`` it under its
        chain hash while live, then ``free`` it — which parks it in the
        reuse LRU, content-addressable exactly like a locally-prefilled
        published block.  Already-cached hashes are skipped (first
        publisher wins); allocation pressure stops the import early
        (partial chains are still useful from the root).  Returns the
        number of blocks imported."""
        self._check_transferable("import_blocks")
        geom = (payload.get("block_size"), payload.get("layers"),
                payload.get("kv_heads"), payload.get("head_dim"),
                payload.get("dtype"))
        want = (self.bs, self.L, self.KV, self.D,
                self._cache_dtype)
        if geom != want:
            raise ValueError(
                f"import_blocks: payload geometry {geom} does not match "
                f"this engine's cache geometry {want} (block_size, layers, "
                "kv_heads, head_dim, dtype) — transfers require identical "
                "cache layouts")
        imported = 0
        for h, kv in payload.get("blocks", {}).items():
            if self.blocks.lookup(h) is not None:
                continue
            if not self.blocks.can_allocate(1):
                break
            (b,) = self.blocks.allocate(1)
            self._write_block(b, kv["k"], kv["v"])
            self.blocks.publish(b, h)
            self.blocks.free([b])   # park published: reusable, evictable
            imported += 1
        return imported

    def import_blocks_packed(self, header: Dict, raw: bytes) -> int:
        """Install an ``export_blocks_packed`` chain segment: validate
        the self-describing geometry header AND that the raw byte count
        matches what the geometry implies BEFORE touching the cache — a
        torn/truncated buffer is a typed ValueError, never a wrong or
        half-imported block — then allocate/write/publish/free exactly
        like :meth:`import_blocks`.  Returns the imported count."""
        self._check_transferable("import_blocks_packed")
        geom = (header.get("block_size"), header.get("layers"),
                header.get("kv_heads"), header.get("head_dim"),
                header.get("dtype"))
        want = (self.bs, self.L, self.KV, self.D,
                self._cache_dtype)
        if geom != want:
            raise ValueError(
                f"import_blocks_packed: payload geometry {geom} does not "
                f"match this engine's cache geometry {want} (block_size, "
                "layers, kv_heads, head_dim, dtype) — transfers require "
                "identical cache layouts")
        hashes = [str(h) for h in header.get("hashes") or ()]
        shape = [2, self.L, len(hashes), self.KV, self.bs, self.D]
        if list(header.get("shape") or ()) != shape:
            raise ValueError(
                f"import_blocks_packed: header shape "
                f"{header.get('shape')} does not match the geometry-"
                f"implied {shape}")
        dt = _np_dtype(str(header["dtype"]))
        expect = 1
        for dim in shape:
            expect *= int(dim)
        expect *= dt.itemsize
        if len(raw) != expect:
            raise ValueError(
                f"import_blocks_packed: payload is {len(raw)} bytes but "
                f"the geometry implies {expect} — truncated or padded "
                "buffer rejected whole")
        arr = np.frombuffer(raw, dtype=dt).reshape(shape)
        imported = 0
        for i, h in enumerate(hashes):
            if self.blocks.lookup(h) is not None:
                continue
            if not self.blocks.can_allocate(1):
                break
            (b,) = self.blocks.allocate(1)
            self._write_block(b, [arr[0, li, i] for li in range(self.L)],
                              [arr[1, li, i] for li in range(self.L)])
            self.blocks.publish(b, h)
            self.blocks.free([b])
            imported += 1
        return imported

    def pull_blocks(self, peer_endpoint: str, hashes: Sequence[str], *,
                    epoch: Optional[int] = None,
                    timeout: float = 60.0) -> Tuple[int, int]:
        """Pull a chain segment DIRECTLY off a peer's data-plane
        listener (inference/blockwire.py) and import it — the
        destination side of the one-hop transfer; the frontend only
        ever orchestrates this with directory-sized control messages.
        Returns ``(blocks_imported, payload_bytes)``.  Raises
        ``StaleEpoch`` when the peer fenced the handshake, ``WireError``
        for transport faults — callers degrade to the frontend relay."""
        from .blockwire import default_pool

        header, raw = default_pool().pull(peer_endpoint, list(hashes),
                                          epoch=epoch, timeout=timeout)
        return self.import_blocks_packed(header, raw), len(raw)

    def _write_block(self, dst: int, ks: Sequence[np.ndarray],
                     vs: Sequence[np.ndarray]):
        """Device-side write of one imported block across every layer's
        K and V cache (same shape of program as the COW copy: the block
        id is data, so one compiled write program serves every import)."""
        if self._put_fn is None:
            if "put" not in self._programs:
                stacked = self.cache_spec.stacked

                def put(kcs, vcs, d, ks, vs):
                    if stacked:
                        return (kcs.at[:, d].set(jnp.stack(ks)),
                                vcs.at[:, d].set(jnp.stack(vs)))
                    kcs = [kc.at[d].set(k) for kc, k in zip(kcs, ks)]
                    vcs = [vc.at[d].set(v) for vc, v in zip(vcs, vs)]
                    return kcs, vcs
                self._programs["put"] = jax.jit(put, donate_argnums=(0, 1))
            self._put_fn = self._programs["put"]
        self.key_caches, self.value_caches = self._put_fn(
            self.key_caches, self.value_caches, jnp.asarray(dst, jnp.int32),
            [jnp.asarray(k) for k in ks], [jnp.asarray(v) for v in vs])
