"""What ``ServingEngine`` asks of a model it serves.  The engine knows no
architecture: it owns slots, the block pool, the token budget, the scans and
sampling, and asks the model three questions:

``serving_weights(dtype)``
    the weight pytree the compiled programs take as an argument.  It has a
    ``"head"`` leaf ``[hidden, vocab]``: the engine heads the rows it samples;
    a model whose head IS its table ``"embed"`` ``[vocab, hidden]`` may name no
    ``"head"``, and the engine heads by the table (``serving.head_logits``).
``serving_cache_spec()``
    a :class:`CacheSpec`: which arrays a CACHE layer keeps in the block pool
    and the shape of one block of each, so the engine can allocate the pool,
    copy a block (COW), export it and key its compiled programs.  ``layers``
    counts cache layers, which need not be the weights' layers: a model that
    runs its stack of layers several times over the same weights keeps a
    cache a (pass, layer), ``passes x depth`` of them.
``serving_trunk(block_size=, cache_quant=)``
    a pure function ``trunk(weights, caches, rope, token_ids, enc, dec, now,
    cu, bt, mq, scales) -> (hidden, caches, new_scales, counts)``: packed
    tokens through every layer against the paged cache, final norm applied.
    ``caches`` is a tuple with one entry for every array of the spec: a list
    of ``[num_blocks, *block]`` arrays, a cache layer each, which a trunk
    that the compiler sees unrolled indexes from Python; or, where the spec
    says ``stacked``, ONE array ``[layers, num_blocks, *block]``, which a
    trunk whose layers are a loop in the compiled program writes and reads
    at a traced layer index (ops/paged_attention.py ``layer=``).  ``counts``
    is a dict of int32 scalars the engine adds to its counters of the same
    names (``{}``: none).  A trunk takes its counts from its ops, which choose
    their own kernels and say what they did (``paged_counts``, ``latent_counts``,
    ``held_experts``).  It seeds the names its result block carries.

and ``serving_rope(max_seq_len)`` for the table the trunk reads positions
from.  ``LlamaForCausalLM`` (models/llama.py: a list of per-head pools),
``PanguUltraMoEForCausalLM`` (models/pangu_moe.py: a list of latent pools),
``OuroForCausalLM`` (models/ouro.py: one stacked per-head pool of
``total_ut_steps x num_hidden_layers`` cache layers) and
``DeepseekV32ForCausalLM`` (models/deepseek_v32.py: TWO arrays a layer of
different width and meaning, a latent entry and a selector's key, under one
block table) and ``Lfm2MoeForCausalLM`` (models/lfm2_moe.py: per-head pools
for its attention layers alone and STATE A SLOT for its conv layers, below)
answer them.  The arrays of a spec are positional: whatever the
engine does to a block (allocate, copy on write, keep for a shared prefix,
free) it does to that block of every array of every layer OF ONE KIND.

A KIND of cache layer (``CacheSpec.kinds``; a spec that names none has one,
all its layers) is a set of cache layers that share a lifetime: its own pool
(the arrays' shapes are the spec's, the number of blocks the kind's:
``ServingEngine(num_blocks={kind: n})``), its own ``BlockManager`` and its own
block table ``[B, P]``.  The trunk of a spec with several kinds is handed
``bt`` as a TUPLE of tables in the kinds' order and ``caches`` with the
kinds' layers one after another (the first kind's first), and calls each
layer's attention with its kind's table.  A position keeps its logical place
in every table: entry ``p // block_size``.  The FIRST kind keeps every
position of a request and is booked as the one pool always was: all of a
request's blocks at admission, freed when it ends.  A further kind with a
``window`` (``SmallThinkerForCausalLM``, models/smallthinker.py: 4,096
positions in three layers of four) keeps the blocks that hold a row's last
``window`` positions and GIVES THE REST BACK while the row runs: a launch
takes the blocks it may write up to its reach before it starts, and after its
harvest every block that lies wholly under ``cached - window + 1`` goes back
through ``BlockManager.free`` (refcounted, so a shared block outlives one
owner) and its table entry reads as no block; there is no ring.  Admission
RESERVES such a kind's worst hold, ``min(blocks of the request, ceil((window
+ a launch's reach) / block_size) + 1)``, and the queue's head waits while a
pool's reservations are full, so a running row is never short of a block and
nothing is preempted from inside a launch; ``evict`` and a recompute from 0
walk the window forward again and free as they go.  What takes a request's
blocks to be ALL its positions does not hold for a spec of several kinds,
and the engine refuses each with a typed error that says ``why_not``: the
prefix cache (a published prefix has lost its window layers' blocks),
speculation, block export and import; ``cache_quant="int8"`` by
``quantizable``.

State WITHOUT positions is the second kind (``CacheSpec.slot_state``): a
fixed-size state a batch SLOT a layer, whatever the context's length (a short
convolution's last inputs; ``LFM2-MoE``, models/lfm2_moe.py).  The engine owns
one array ``[layers_of_that_kind, max_batch_size, *shape]`` for each (zeros at
construction, the cache's type), hands them to the trunk in ``caches`` AFTER
the pool arrays and takes them back in the same places, donated and carried
through the scans like the pool.  Rows of the trunk's arguments (``dec``,
``now``, ``cu``, ``bt``) ARE slots, so a token finds its state by its row.
The contract for such state: a row fed ``now`` tokens at ``dec`` reads what
the positions ``dec - 1, dec - 2, ..`` left there and writes back what
``dec + now`` will read; a position under 0 reads zero BY POSITION, so a new
tenant (or a request recomputed from 0 after ``evict``) never sees the last
one's state and nothing is ever reset; a row that feeds nothing keeps its
state, and the engine keeps it for a row its scan has frozen.  What takes a
request's state to be its blocks does not hold for it, and the engine refuses
each with a typed error that says ``why_not``: the prefix cache (adopted
blocks start a request at position n with no state for n - 1), speculation
(a refused draft would have advanced the state), block export and import."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

__all__ = ["CacheSpec", "CacheKind"]


@dataclass(frozen=True)
class CacheKind:
    """Cache layers that share a pool, a block table and a lifetime (module
    docstring)."""
    name: str
    layers: int
    window: Optional[int] = None  # positions a layer attends; None: all of them


@dataclass(frozen=True)
class CacheSpec:
    # ((name, block_shape(block_size) -> tuple), ...): one pool array
    # [num_blocks, *block_shape] a layer for each
    arrays: Tuple[Tuple[str, Callable[[int], tuple]], ...]
    layers: int                   # CACHE layers (module docstring)
    # everything of the model that shapes the trunk's trace
    key: tuple
    # kv heads and head size of a per-head K/V cache (the block wire header)
    kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    quantizable: bool = True      # cache_quant="int8"
    transferable: bool = True     # export_blocks* / import_blocks* / blockwire
    why_not: str = ""             # said by the typed refusals
    stacked: bool = False         # the pool is one array with a leading layer axis
    passes: int = 1               # times an iteration runs the weights' layers
    # state a SLOT (module docstring): ((name, layers that keep it, shape of
    # one slot's state in one layer), ...); ``layers`` above stays the POOLED
    # cache layers.  The model puts it into ``key`` too.
    slot_state: Tuple[Tuple[str, int, tuple], ...] = ()
    # kinds of cache layer (module docstring), ``layers`` their sum and the
    # first without a window; (): one kind, every layer.  In ``key`` too.
    kinds: Tuple[CacheKind, ...] = ()

    def __post_init__(self):
        if self.kinds and (sum(k.layers for k in self.kinds) != self.layers
                           or self.kinds[0].window is not None or self.stacked
                           or len({k.name for k in self.kinds}) != len(self.kinds)):
            raise ValueError(
                f"kinds {self.kinds} of a spec of {self.layers} cache layers: distinct "
                "names, layers that add up, the first kind without a window, no stacked pool")

    @property
    def blocks_are_positions(self) -> bool:
        """Whether a request's blocks of the first kind are ALL its state: no
        state a slot and no further kind with a lifetime of its own."""
        return not self.slot_state and len(self.kinds) <= 1
