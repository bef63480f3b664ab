"""Compiled pipeline parallelism — the whole microbatch schedule in ONE XLA
program, for REAL models (heterogeneous stages, tied embeddings, stateful
optimizers).

Reference analog: the static-graph pipeline scheduler passes
(/root/reference/python/paddle/distributed/passes/pipeline_scheduler_pass/)
which compile 1F1B/ZB orderings into a single program per rank, plus
SharedLayerDesc's shared-grad allreduce
(/root/reference/python/paddle/distributed/fleet/meta_parallel/parallel_layers/pp_layers.py:76).

TPU-native formulation (the GSPMD/shard_map pipeline):

* **Partial-manual shard_map**: only the 'pp' axis is manual
  (``jax.shard_map(..., axis_names={'pp'})``); dp/mp/sharding stay AUTO
  inside, so the model's GSPMD sharding annotations (TP layers'
  ``with_sharding_constraint``) keep working verbatim inside the pipeline
  body and XLA inserts the mp collectives — no manual rewrite of the layer
  library.
* **Head/body/tail decomposition**: a real LM pipeline is [embedding]
  + P×[k uniform decoder layers] + [norm+lm_head]. The homogeneous BODY is
  stacked ``[P, ...]`` and pp-sharded — each device holds exactly its
  stage's decoder weights. The HEAD (first-stage prefix) and TAIL
  (last-stage suffix) ride as ordinary pp-replicated (auto) arrays; under
  SPMD every rank executes head/tail in lockstep and masks by the stage
  id (a pp-sharded arange argument), so the redundant compute costs no
  wall-clock
  (all ranks would be in that program region anyway) and ``jnp.where``
  keeps gradients exact.
* **Tied embeddings (SharedLayerDesc)**: the shared layer's weight enters
  the program ONCE as an auto array used by both the head lookup (live on
  stage 0) and the tail logits matmul (live on stage P-1); shard_map's
  reverse rule psums the cotangent over the manual 'pp' axis — exactly the
  reference's shared-grad allreduce, derived by AD instead of hand-wired.
* **Schedule**: activations advance around the pp ring with
  ``lax.ppermute`` inside a ``lax.scan`` over T = num_micro + P - 1 ticks;
  XLA's latency-hiding scheduler overlaps the ppermute with the next tick's
  compute. Per-tick ``jax.checkpoint`` keeps saved state to stage-boundary
  activations (1F1B-grade memory).

Composes with TrainStep: stacked body weights + head/tail params form the
parameter set; the optimizer's param groups are REWIRED onto them (per-group
hyperparameters preserved — group membership must be uniform across stages
for each body slot) and any pre-existing accumulator/master state is
restacked ``[P, ...]`` so a mid-training switch to the compiled engine keeps
optimizer momentum.

VPP chunks (num_chunks > 1) compile too: weights stack [C, P, ...] (dim 0 =
virtual chunk). Two schedules exist:

* **Interleaved-1F1B (default when legal)**: ONE scan whose stage-0 feed
  alternates chunks in groups of P microbatches (Megatron's interleaved
  order), reaching a (P-1)/C bubble. The tick body is BRANCH-FREE: the
  active chunk's weights are selected from the stacked [C, P, ...] arrays
  with ``lax.dynamic_index_in_dim`` — one fused program per tick, no
  ``lax.switch`` over per-chunk branches (the r5 switch formulation paid
  +43% steady-state per-microbatch time, measured at r5 and r6).
  Requires ``num_micro % P == 0``. Chunk-program homogeneity is a hard
  constructor invariant (every schedule path runs ONE body program per
  tick); ``PADDLE_TPU_VPP_INTERLEAVED_IMPL=switch`` selects ``lax.switch``
  weight selection instead of the gather, for A/B profiling of the
  branch cost.
* **Chunk-sequential rings**: each microbatch set circles the pp ring once
  per chunk, exits hopping from the last stage back to stage 0 via one
  extra ppermute; bubble ~(P-1) microbatch-times. Forced with
  ``PADDLE_TPU_VPP_INTERLEAVED=0`` and used whenever the interleaved feed
  cannot tile (``num_micro % P != 0``).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from ...multihost import global_device_put

from ....autograd import tape
from ....nn.layer.layers import Layer
from ....tensor.tensor import Tensor

__all__ = ["CompiledPipelineTrainStep", "pipeline_bubble_fraction"]


def pipeline_bubble_fraction(num_micro: int, num_stages: int) -> float:
    """Idle fraction of the synchronous pipeline: (P-1)/(M+P-1)."""
    return (num_stages - 1) / (num_micro + num_stages - 1)


def _shard_map_pp(fn, mesh, in_specs, out_specs):
    """Manual over 'pp' only; every other mesh axis stays auto (GSPMD)."""
    from ...shard_map_compat import shard_map_manual

    return shard_map_manual(fn, mesh, in_specs, out_specs, {"pp"})


def _pp_collectives_native(mesh) -> bool:
    """Whether the ring collectives lower inside partial-manual shard_map
    over 'pp' on this jax (see shard_map_compat.partial_manual_supported —
    the constructor refuses unsupported meshes up front because the
    failure mode is a fatal XLA abort, not an exception)."""
    from ...shard_map_compat import partial_manual_supported

    return partial_manual_supported(mesh, {"pp"})


def _layer_sig(layer, ffunc):
    cfg = repr(layer) if isinstance(layer, Layer) else getattr(
        layer, "__name__", str(layer))
    fid = ffunc if isinstance(ffunc, str) or ffunc is None else getattr(
        ffunc, "__qualname__", repr(ffunc))
    return (type(layer).__name__, cfg, fid)


class _Swap:
    """Temporarily install traced values into param Tensors."""

    def __init__(self, tensors, values):
        self.tensors, self.values = tensors, values

    def __enter__(self):
        self.saved = [t._value for t in self.tensors]
        for t, v in zip(self.tensors, self.values):
            t._value = v

    def __exit__(self, *exc):
        for t, v in zip(self.tensors, self.saved):
            t._value = v
        return False


class _Segment:
    """A contiguous run of (layer, fwd_func) pairs + its parameter list."""

    def __init__(self, pairs: Sequence[Tuple]):
        self.pairs = list(pairs)
        self.params: List[Tensor] = []
        seen = set()
        for layer, _ in self.pairs:
            if isinstance(layer, Layer):
                for p in layer.parameters():
                    if id(p) not in seen:
                        seen.add(id(p))
                        self.params.append(p)

    def sig(self):
        return ([_layer_sig(l, f) for l, f in self.pairs]
                + [(tuple(p.shape), str(p.dtype)) for p in self.params])

    def run(self, param_leaves, x_val):
        """Pure function: swap in leaves, run the chain on a raw value."""
        with _Swap(self.params, list(param_leaves)):
            t = Tensor(x_val, stop_gradient=True)
            for layer, ffunc in self.pairs:
                if ffunc == "plain_fn":
                    t = layer(t)
                elif ffunc is not None:
                    t = ffunc(layer, t)
                else:
                    t = layer(t)
            return t._value


def _decompose(pipe) -> Tuple[_Segment, List[_Segment], _Segment]:
    """Split the pipeline's stages into (head, per-stage body, tail).

    The body layer type is the one whose instances appear on more than one
    stage (the repeated trunk — e.g. the decoder layer); the head is stage
    0's prefix before its first body layer, the tail is the last stage's
    suffix after its last body layer. Every stage must carry the same number
    of body layers with identical signatures."""
    n_seg = pipe._num_segments  # = num_stages * num_chunks (VPP)
    P = pipe._num_stages
    pairs = [list(zip(pipe._stage_layers[s], pipe._stage_fwd_funcs[s]))
             for s in range(n_seg)]
    shared_ids = {id(l) for l in pipe._shared_layers.values()}
    type_stages: Dict[str, set] = {}
    for s in range(n_seg):
        for layer, _ in pairs[s]:
            if id(layer) in shared_ids:
                continue  # one OBJECT on many stages (tied weights) ≠ a body
            type_stages.setdefault(type(layer).__name__, set()).add(s)
    body_types = {t for t, ss in type_stages.items() if len(ss) == n_seg}
    if not body_types and n_seg > 1:
        # fall back: types on >1 stage (short pipes where the trunk doesn't
        # reach every stage can't be stacked)
        body_types = {t for t, ss in type_stages.items() if len(ss) > 1}
    if not body_types:
        raise ValueError(
            "compiled pipeline: no layer type spans multiple stages — cannot "
            "identify a homogeneous body to stack; use the eager engine")

    def is_body(layer):
        return id(layer) not in shared_ids and type(layer).__name__ in body_types

    head_pairs, first_body = [], None
    for i, (layer, f) in enumerate(pairs[0]):
        if is_body(layer):
            first_body = i
            break
        head_pairs.append((layer, f))
    if first_body is None:
        raise ValueError("compiled pipeline: stage 0 has no body layers")

    tail_pairs, last_body = [], None
    for i in range(len(pairs[-1]) - 1, -1, -1):
        if is_body(pairs[-1][i][0]):
            last_body = i
            break
    if last_body is None:
        raise ValueError(f"compiled pipeline: segment {n_seg - 1} has no body layers")
    tail_pairs = pairs[-1][last_body + 1:]

    body_segs = []
    for s in range(n_seg):
        lo = first_body if s == 0 else 0
        hi = last_body + 1 if s == n_seg - 1 else len(pairs[s])
        seg_pairs = pairs[s][lo:hi]
        if any(not is_body(l) for l, _ in seg_pairs):
            raise ValueError(
                f"compiled pipeline: stage {s} interleaves body and non-body "
                "layers; head/tail must be contiguous prefixes/suffixes")
        body_segs.append(_Segment(seg_pairs))

    ref = body_segs[0].sig()
    for s in range(1, n_seg):
        if body_segs[s].sig() != ref:
            raise ValueError(
                f"compiled pipeline needs a homogeneous body; stage {s} "
                f"{body_segs[s].sig()} != stage 0 {ref}. Choose a seg_method "
                "that gives every stage the same decoder count")
    return _Segment(head_pairs), body_segs, _Segment(tail_pairs)


def _full_mesh_put(p: Tensor, mesh):
    """Move a head/tail param from its stage submesh onto the full mesh,
    keeping axis-name sharding dims that exist there (mp etc.)."""
    if isinstance(p._value, jax.core.Tracer):
        return
    try:
        old = p._value.sharding.spec
    except Exception:
        old = None
    spec = PartitionSpec(*[
        e if (e in mesh.axis_names or isinstance(e, tuple)) else None
        for e in (old or [None] * p.ndim)
    ]) if old else PartitionSpec(*([None] * p.ndim))
    p._value = global_device_put(np.asarray(p._value), NamedSharding(mesh, spec))


class _PipeParams(Layer):
    """Parameter container the TrainStep compiles against: stacked body
    weights — [P, ...] pp-sharded, or [C, P, ...] with VPP chunks (dim 0 =
    virtual chunk, dim 1 = pp) — plus the head/tail params."""

    def __init__(self, body_segs: List[_Segment], aux_params: List[Tensor],
                 mesh, num_stages: int):
        super().__init__()
        self._mesh = mesh
        P = num_stages
        C = len(body_segs) // P
        self.num_chunks = C
        self.stacked: List[Tensor] = []
        self.stacked_specs: List[PartitionSpec] = []
        for j, p0 in enumerate(body_segs[0].params):
            vals = np.stack([np.asarray(seg.params[j]._value) for seg in body_segs])
            try:
                inner = tuple(
                    e if (e in mesh.axis_names and e != "pp") or isinstance(e, tuple)
                    else None
                    for e in (p0._value.sharding.spec or ()))
            except Exception:
                inner = ()
            inner = tuple(inner) + (None,) * (p0.ndim - len(inner))
            if C > 1:
                # segment g = c*P + d  ->  [C, P, ...]
                vals = vals.reshape(C, P, *vals.shape[1:])
                spec = PartitionSpec(None, "pp", *inner)
            else:
                spec = PartitionSpec("pp", *inner)
            sh = NamedSharding(mesh, spec)
            t = Tensor(global_device_put(vals, sh), stop_gradient=False)
            t.name = f"pipe_stacked_{j}"
            self.stacked.append(t)
            self.stacked_specs.append(spec)
            setattr(self, f"w{j}", t)  # registers as parameter
        self.aux: List[Tensor] = list(aux_params)
        for k, p in enumerate(self.aux):
            _full_mesh_put(p, mesh)
            setattr(self, f"aux{k}", p)

    def parameters(self, include_sublayers=True):
        return list(self.stacked) + list(self.aux)


def _remesh_value(v, mesh):
    """Move a pre-existing state array from a stage submesh onto the full
    mesh, keeping sharding dims whose axis names exist there."""
    try:
        old = v.sharding.spec
    except Exception:
        old = None
    spec = PartitionSpec(*[
        e if (e in mesh.axis_names or isinstance(e, tuple)) else None
        for e in (old or [None] * np.ndim(v))
    ]) if old else PartitionSpec(*([None] * np.ndim(v)))
    return global_device_put(np.asarray(v), NamedSharding(mesh, spec))


def _rewire_optimizer(optimizer, body_segs: List[_Segment],
                      stacked: List[Tensor], aux_ids: set, mesh,
                      stacked_specs: List[PartitionSpec], num_stages: int):
    """Re-point param groups at stacked weights (per-group hyperparameters
    kept) and restack any pre-existing optimizer state [P, ...] (or
    [C, P, ...] with VPP chunks, matching _PipeParams)."""
    P = len(body_segs)  # total SEGMENTS = num_stages * num_chunks
    C = P // num_stages
    slot_of: Dict[int, Tuple[int, int]] = {}
    for s, seg in enumerate(body_segs):
        for j, p in enumerate(seg.params):
            slot_of[id(p)] = (s, j)

    # group membership per body slot, from each group's original params
    group_of_slot: Dict[int, int] = {}
    for gi, g in enumerate(optimizer._param_groups):
        for p in g["params"]:
            slot = slot_of.get(id(p))
            if slot is None:
                continue
            s, j = slot
            prev = group_of_slot.setdefault(j, gi)
            if prev != gi:
                raise ValueError(
                    f"compiled pipeline: body slot {j} belongs to different "
                    f"param groups on different stages ({prev} vs {gi}); "
                    "group membership must be uniform across stages")

    new_groups = []
    for gi, g in enumerate(optimizer._param_groups):
        new_params, seen = [], set()
        for p in g["params"]:
            slot = slot_of.get(id(p))
            if slot is not None:
                j = slot[1]
                if j not in seen and group_of_slot[j] == gi:
                    seen.add(j)
                    new_params.append(stacked[j])
            else:
                # aux (head/tail) params and any params outside the pipeline
                # stay as-is (aux already re-placed by _full_mesh_put)
                new_params.append(p)
        new_groups.append({**{k: v for k, v in g.items() if k != "params"},
                           "params": new_params})
    optimizer._param_groups = new_groups
    optimizer._parameter_list = [p for g in new_groups for p in g["params"]]

    # restack pre-existing state so momentum survives the engine switch
    def restack(d: Dict[int, jnp.ndarray], j: int, target: Tensor):
        vals, found = [], 0
        for s in range(P):
            v = d.pop(id(body_segs[s].params[j]), None)
            if v is not None:
                found += 1
            vals.append(v)
        if found == 0:
            return
        if found != P:
            raise ValueError(
                f"compiled pipeline: optimizer state for body slot {j} exists "
                f"on {found}/{P} stages — cannot restack partial state")
        if np.ndim(vals[0]) == 0:
            # scalar accumulators (step counters like beta_pow) advanced in
            # lockstep across stages — keep one, don't stack (stacking would
            # break broadcasting against the [P, ...] moments)
            d[id(target)] = global_device_put(
                np.asarray(vals[0]), NamedSharding(mesh, PartitionSpec()))
            return
        # per-stage values live on different stage submeshes — stack on host
        arr = np.stack([np.asarray(v) for v in vals])
        if C > 1:
            arr = arr.reshape(C, num_stages, *arr.shape[1:])  # match [C,P,...]
        spec = (stacked_specs[j] if arr.ndim == len(stacked_specs[j])
                else PartitionSpec(*([None] * arr.ndim)))
        d[id(target)] = global_device_put(arr, NamedSharding(mesh, spec))

    for name, d in optimizer._accumulators.items():
        for j, t in enumerate(stacked):
            restack(d, j, t)
        # head/tail params moved to the full mesh — their existing state must
        # follow or jit sees mixed device sets
        for pid in list(d):
            if pid in aux_ids:
                d[pid] = _remesh_value(d[pid], mesh)
    for j, t in enumerate(stacked):
        restack(optimizer._master_weights, j, t)
    for pid in list(optimizer._master_weights):
        if pid in aux_ids:
            optimizer._master_weights[pid] = _remesh_value(
                optimizer._master_weights[pid], mesh)


class CompiledPipelineTrainStep:
    """loss + grads + optimizer update for the FULL microbatch pipeline
    schedule, compiled into one donated-buffer XLA program. Handles
    heterogeneous stages (embedding head / lm-head tail), SharedLayerDesc
    tied weights, and optimizers with existing state / multiple groups.

    VPP schedule selection (r6): with ``num_chunks > 1`` the interleaved
    ordering is chosen AUTOMATICALLY when ``num_micro % num_stages == 0``
    (chunk-program homogeneity is a constructor invariant — every
    schedule runs one body program per tick); its
    tick body is branch-free — the active chunk's weights are gathered
    from the stacked ``[C, P, ...]`` parameters with
    ``lax.dynamic_index_in_dim`` instead of ``lax.switch`` over per-chunk
    branches, which erased the r5 switch tick's +43% steady-state
    per-microbatch tax (measured at r6). Chunk-sequential rings remain the
    fallback (and can be forced with ``PADDLE_TPU_VPP_INTERLEAVED=0``);
    ``PADDLE_TPU_VPP_INTERLEAVED_IMPL=switch`` selects ``lax.switch``
    weight selection for A/B profiling of the branch cost. Optimizer
    state restacks ``[C, P, ...]`` alongside the
    weights and round-trips through :meth:`sync_to_model` unchanged under
    either schedule."""

    def __init__(self, pipe, optimizer, num_micro: int, scaler=None, remat: bool = True):
        from ....jit.api import TrainStep
        from ...topology import get_hybrid_communicate_group
        from .pipeline_parallel import PipelineParallel

        model = pipe._layers if isinstance(pipe, PipelineParallel) else pipe
        hcg = get_hybrid_communicate_group()
        if hcg is None or hcg.axis_size("pp") <= 1:
            raise ValueError("compiled pipeline needs an active mesh with pp > 1")
        self.mesh = mesh = hcg.mesh
        if not _pp_collectives_native(mesh):
            # on old jax the SPMD partitioner ABORTS the process (fatal
            # check, not an exception) when the ring collectives' backward
            # meets a real auto axis — refuse cleanly up front
            raise NotImplementedError(
                "compiled pipeline: this jax version cannot mix the manual "
                "'pp' axis with size>1 auto mesh axes (dp/mp/sharding) — "
                "XLA's SPMD partitioner aborts on the ring collectives' "
                "backward. Use a pp-only mesh (dp=mp=sharding=1) or a jax "
                "with top-level jax.shard_map (>=0.8).")
        self.num_micro = num_micro
        self.num_stages = P = model._num_stages
        # VPP: C virtual chunks per device, weights [C, P, ...]; the compiled
        # schedule runs chunk-SEQUENTIAL rings (each microbatch set circles
        # the ring once per chunk, exits hop from the last stage back to
        # stage 0). The interleaved-1F1B ORDERING is a scheduling choice the
        # reference makes explicitly; here cross-chunk overlap is left to
        # XLA's scheduler within the single program — the memory/partition
        # semantics (per-device virtual stages) are the VPP contract kept.
        C = self.num_chunks = model._num_chunks
        self._pipe = model
        if model._loss_fn is None:
            raise ValueError("PipelineLayer built without loss_fn")
        loss_fn_t = model._loss_fn

        head, body_segs, tail = _decompose(model)
        self._body_segs = body_segs
        # chunk-program homogeneity: EVERY schedule path (branch-free
        # gather, lax.switch, chunk-sequential rings) compiles body0's ONE
        # program and varies only the weights, which is only sound when
        # segment c*P + d runs the same program for every chunk c.
        # _decompose's body check guarantees this today; re-checked as a
        # hard error so a future relaxation of _decompose (e.g. per-chunk
        # special layers) cannot silently mis-run chunks through any of
        # the schedules — all of them would need extending first.
        self._chunks_homogeneous = all(
            body_segs[c * P + d].sig() == body_segs[d].sig()
            for c in range(C) for d in range(P))
        if not self._chunks_homogeneous:
            raise ValueError(
                "compiled pipeline: chunk programs differ across virtual "
                "chunks; every schedule runs one body program per tick — "
                "heterogeneous chunks are not supported")
        # head/tail params deduped — a SharedLayerDesc layer appearing in
        # both (tied embedding) enters the program exactly once
        aux, seen = [], set()
        for p in head.params + tail.params:
            if id(p) not in seen:
                seen.add(id(p))
                aux.append(p)
        self._params_layer = _PipeParams(body_segs, aux, mesh, P)
        stacked = self._params_layer.stacked
        n_stacked = len(stacked)
        n_aux = len(aux)
        aux_index = {id(p): k for k, p in enumerate(aux)}
        head_idx = [aux_index[id(p)] for p in head.params]
        tail_idx = [aux_index[id(p)] for p in tail.params]

        _rewire_optimizer(optimizer, body_segs, stacked, set(aux_index), mesh,
                          self._params_layer.stacked_specs, P)

        body0 = body_segs[0]

        # ring activation shape = the body input (head output when a head
        # exists, else the data microbatch itself)
        self._head = head
        self._tail = tail

        stk_specs = tuple(
            PartitionSpec("pp") if C == 1 else PartitionSpec(None, "pp")
            for _ in range(n_stacked))

        def local(stacked_vals, aux_vals, xs, ys, stage_ids):
            # stage index arrives as a pp-sharded arange(P) argument — each
            # device sees its own id — instead of lax.axis_index('pp'),
            # which older jax cannot lower next to real auto axes
            stage = stage_ids[0]
            head_vals = [aux_vals[k] for k in head_idx]
            tail_vals = [aux_vals[k] for k in tail_idx]
            M = xs.shape[0]
            T = M + P - 1

            def run_head(x):
                return head.run(head_vals, x) if head.pairs else x

            body_fwd = (jax.checkpoint(body0.run) if remat else body0.run)
            ring_perm = [(i, (i + 1) % P) for i in range(P)]

            def ring_shift(v):
                """Advance v one hop around the pp ring (stage s receives
                stage s-1's value)."""
                return lax.ppermute(v, "pp", ring_perm)

            def run_chunk(p_chunk, xs_in, first_chunk):
                def tick(h, t):
                    x_t = lax.dynamic_index_in_dim(xs_in, jnp.clip(t, 0, M - 1),
                                                   0, keepdims=False)
                    inp0 = run_head(x_t) if first_chunk else x_t
                    inp = jnp.where(stage == 0, inp0, h)
                    out = body_fwd(p_chunk, inp)
                    return ring_shift(out), out

                h_struct = jax.eval_shape(
                    run_head if first_chunk else (lambda v: v), xs_in[0])
                h0 = jnp.zeros(h_struct.shape, h_struct.dtype)
                _, outs = lax.scan(tick, h0, jnp.arange(T))
                # microbatch m exits the LAST stage at tick m + P - 1
                return jnp.take(outs, jnp.arange(M) + P - 1, axis=0)

            import os as _os

            # Schedule selection (r6): the interleaved-VPP ordering is
            # AUTOMATIC whenever it is legal — VPP chunks and a feed that
            # tiles exactly (M % P == 0); chunk-program homogeneity is
            # already a constructor invariant.
            # r5 shipped it opt-in because its per-tick lax.switch over
            # chunk programs cost +43% steady-state per-microbatch time
            # (measured at r5); the r6 tick instead gathers the active
            # chunk's weights from the stacked [C, P, ...] arrays with
            # lax.dynamic_index_in_dim — one fused, branch-free tick body
            # (measured at r6).
            # Env overrides:
            #   PADDLE_TPU_VPP_INTERLEAVED=0  force chunk-sequential rings
            #   PADDLE_TPU_VPP_INTERLEAVED=1  request interleaved (warns
            #       when the schedule is illegal)
            #   PADDLE_TPU_VPP_INTERLEAVED_IMPL=switch  select weights by
            #       lax.switch instead of the gather (A/B isolating the
            #       branch cost — NOT the full r5 tick: the pending-buffer
            #       removal applies to both impls)
            env_il = _os.environ.get("PADDLE_TPU_VPP_INTERLEAVED")
            can_interleave = C > 1 and M % P == 0
            interleave = can_interleave and env_il != "0"
            if env_il == "1" and not can_interleave:
                import warnings

                warnings.warn(
                    f"PADDLE_TPU_VPP_INTERLEAVED=1 ignored: needs VPP "
                    f"chunks (C={C}) and num_micro divisible by pp stages "
                    f"(M={M}, P={P}); running chunk-sequential",
                    stacklevel=2)
            use_indexed = (_os.environ.get(
                "PADDLE_TPU_VPP_INTERLEAVED_IMPL", "indexed") != "switch")
            if interleave:
                # ---- explicit interleaved-VPP ordering (r5):
                # ONE scan whose stage-0 feed alternates chunks in
                # groups of P microbatches — (c, m)'s dependency, chunk
                # c-1's exit of the same microbatch, is fed exactly P ticks
                # earlier and rides the ring's P-1→0 wrap back to stage 0
                # just in time, so the feed is dense (zero stalls) and the
                # whole-schedule bubble is P-1 CHUNK-times = (P-1)/C
                # microbatch-times, the Megatron interleaved bound — instead
                # of the chunk-sequential C*(P-1).
                CM = C * M
                feed_c = np.zeros(CM, np.int32)
                feed_m = np.zeros(CM, np.int32)
                pos = 0
                for blk in range(M // P):
                    for c in range(C):
                        for off in range(P):
                            feed_c[pos] = c
                            feed_m[pos] = blk * P + off
                            pos += 1
                c_arr = jnp.asarray(feed_c)
                m_arr = jnp.asarray(feed_m)
                T_i = CM + P - 1

                if use_indexed:
                    # branch-free body: gather the active chunk's weights
                    # from the [C, 1, ...] local shards INSIDE the remat'd
                    # function — the checkpoint then saves the
                    # loop-invariant stacked arrays (no per-tick gathered
                    # copies) and the backward recomputes the cheap gather
                    def body_idx(stacked_local, c_idx, v):
                        p_c = [lax.dynamic_index_in_dim(a, c_idx, 0,
                                                        keepdims=False)[0]
                               for a in stacked_local]
                        return body0.run(p_c, v)

                    body_idx = jax.checkpoint(body_idx) if remat else body_idx
                else:
                    branches = [
                        (lambda c: (lambda v: body_fwd(
                            [a[c, 0] for a in stacked_vals], v)))(c)
                        for c in range(C)
                    ]

                def itick(h, t):
                    # this stage's work item: the one stage 0 fed s ticks ago
                    ti = jnp.clip(t - stage, 0, CM - 1)
                    my_c = c_arr[ti]
                    my_m = jnp.clip(m_arr[ti], 0, M - 1)
                    x_t = lax.dynamic_index_in_dim(xs, my_m, 0,
                                                   keepdims=False)
                    # the blocked feed is DENSE: (my_c, my_m)'s dependency
                    # — chunk my_c-1's exit of the same microbatch — was
                    # fed exactly P ticks earlier, so its last-stage output
                    # rides the ring's (P-1)→0 wrap and IS the h arriving
                    # at stage 0 THIS tick. No parking buffer is needed
                    # (r6: the r5 formulation carried an [M, ...] pending
                    # scatter/gather through the scan — pure overhead, and
                    # a large share of its +43% steady-state tax).
                    inp0 = jnp.where(my_c == 0, run_head(x_t), h)
                    inp = jnp.where(stage == 0, inp0, h)
                    if use_indexed:
                        out = body_idx(stacked_vals, my_c, inp)
                    else:
                        out = lax.switch(my_c, branches, inp)
                    return ring_shift(out), out

                h_struct = jax.eval_shape(run_head, xs[0])
                h0 = jnp.zeros(h_struct.shape, h_struct.dtype)
                _, outs = lax.scan(itick, h0, jnp.arange(T_i))
                # final-chunk microbatch m finishes the last stage at
                # t_fed(C-1, m) + P - 1
                t_fed = np.zeros(M, np.int64)
                for pos in range(CM):
                    if feed_c[pos] == C - 1:
                        t_fed[feed_m[pos]] = pos
                exit_outs = jnp.take(outs, jnp.asarray(t_fed + P - 1), axis=0)
            else:
                xs_c = xs
                for c in range(C):
                    if C == 1:
                        p_chunk = [a[0] for a in stacked_vals]      # [P,...] local
                    else:
                        p_chunk = [a[c, 0] for a in stacked_vals]   # [C,P,...] local
                    exit_outs = run_chunk(p_chunk, xs_c, c == 0)
                    if c < C - 1:
                        # exits live on the last stage; one ring hop delivers
                        # them to stage 0 as the next chunk's inputs
                        xs_c = ring_shift(exit_outs)
            # merge microbatches for the tail + loss: every rank computes in
            # SPMD lockstep; only the last stage's value survives the mask
            mb = exit_outs.shape[1]
            merged = exit_outs.reshape(M * mb, *exit_outs.shape[2:])
            logits = tail.run(tail_vals, merged) if tail.pairs else merged
            ys_m = ys.reshape(M * ys.shape[1], *ys.shape[2:])
            with tape.no_grad():
                loss = loss_fn_t(Tensor(logits, stop_gradient=True),
                                 Tensor(ys_m, stop_gradient=True))._value
            loss = jnp.where(stage == P - 1, loss.astype(jnp.float32), 0.0)
            return lax.psum(loss, "pp")

        def pipelined_loss(model_, x, y):
            from ....ops.dispatch import apply

            def f(xv, yv, *param_vals):
                stacked_vals = tuple(param_vals[:n_stacked])
                aux_vals = tuple(param_vals[n_stacked:])
                mb = xv.shape[0] // num_micro
                xs = xv.reshape(num_micro, mb, *xv.shape[1:])
                ys = yv.reshape(num_micro, mb, *yv.shape[1:])
                fn = _shard_map_pp(
                    local, mesh,
                    in_specs=(stk_specs, (PartitionSpec(),) * n_aux,
                              PartitionSpec(), PartitionSpec(),
                              PartitionSpec("pp")),
                    out_specs=PartitionSpec())
                stage_ids = jnp.arange(P, dtype=jnp.int32)
                return fn(stacked_vals, aux_vals, xs, ys, stage_ids)

            return apply(f, x, y, *model_.parameters(), op_name="compiled_pipeline")

        self._step = TrainStep(self._params_layer, pipelined_loss, optimizer,
                               scaler=scaler)

    @property
    def bubble_fraction(self) -> float:
        return pipeline_bubble_fraction(self.num_micro, self.num_stages)

    def sync_to_model(self):
        """Write the stacked weights back into the per-stage Tensors and
        re-place head/tail params on their stage submeshes, so the eager
        per-stage engine (state_dict / eval parity) sees a consistent
        placement again. A tied (shared head+tail) param belongs to two
        stages at once and stays on the full mesh — the eager engine treats
        shared layers as one object, so mixed-submesh eager eval of a tied
        model should go through the compiled step instead."""
        from ...multihost import is_multi_controller

        if is_multi_controller():
            # materializing the pp-sharded stack needs shards owned by other
            # processes; use the distributed checkpoint (per-host shards +
            # reshard-on-load) to move state between engines across hosts
            raise NotImplementedError(
                "sync_to_model under multi-controller: save with "
                "paddle_tpu.distributed.save_state_dict (per-host shards) "
                "and reload instead")

        def put_sub(p, sub):
            if sub is None:
                return
            try:
                old = p._value.sharding.spec
            except Exception:
                old = None
            spec = PartitionSpec(*[
                e if e in sub.axis_names else None
                for e in (old or [None] * p.ndim)
            ]) if old else PartitionSpec(*([None] * p.ndim))
            p._value = jax.device_put(np.asarray(p._value), NamedSharding(sub, spec))

        P = self._pipe._num_stages
        for j, t in enumerate(self._params_layer.stacked):
            host = np.asarray(t._value)
            if self.num_chunks > 1:  # [C, P, ...] -> flat segment order
                host = host.reshape(-1, *host.shape[2:])
            for s, seg in enumerate(self._body_segs):
                p = seg.params[j]
                p._value = jnp.asarray(host[s])
                put_sub(p, self._pipe._submeshes[s % P])
        head_ids = {id(p) for p in self._head.params}
        tail_ids = {id(p) for p in self._tail.params}
        shared = head_ids & tail_ids
        for p in self._head.params:
            if id(p) not in shared:
                put_sub(p, self._pipe._submeshes[0])
        for p in self._tail.params:
            if id(p) not in shared:
                put_sub(p, self._pipe._submeshes[self._pipe._num_stages - 1])
        return self._pipe

    def __call__(self, x, y):
        return self._step(x, y)
