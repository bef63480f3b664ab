"""jit.to_static — trace-and-compile (parity: python/paddle/jit/api.py:197).

Capability mapping (SURVEY.md §3.3): the reference needs a PEP-523 bytecode
tracer (SOT) + PIR programs + an interpreter because Python is opaque to its
compiler. Here Python IS the tracer: the eager op layer runs unchanged on jax
tracers, so to_static = run the function under jax.jit with parameters,
buffers, RNG key, and inputs as traced arguments. The SOT guard discipline
(executor_cache.py guards) survives as the specialization cache key:
(input treedef, shapes, dtypes, training flag, amp state).

Backward: calling .backward() on outputs of a compiled forward executes a
second jitted function that recomputes forward + backward in one XLA program
(rematerialization — the TPU-favored memory/compute tradeoff). For peak
training throughput use paddle_tpu.jit.TrainStep, which compiles loss + grads
+ optimizer update into a single donated-buffer step.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..autograd import tape
from ..framework.random import default_generator
from ..profiler import SETUP, RecordEvent, SetupSpan
from ..tensor.tensor import Tensor
from . import trace_state

__all__ = ["to_static", "not_to_static", "StaticFunction", "ignore_module", "TrainStep", "InputSpec"]

# jit.enable_to_static(False) falls every StaticFunction back to eager
_to_static_enabled = True

# exceptions that mean "this Python is untraceable", not "user bug": the
# graph-break conditions of the reference's SOT (opcode_executor.py:1594)
_TRACE_BREAK_ERRORS = tuple(
    getattr(jax.errors, n)
    for n in (
        "TracerArrayConversionError",
        "TracerBoolConversionError",
        "TracerIntegerConversionError",
        "ConcretizationTypeError",
        "UnexpectedTracerError",
    )
    if hasattr(jax.errors, n)
)


class InputSpec:
    """paddle.static.InputSpec parity (shape with None for dynamic dims)."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name
        self.stop_gradient = stop_gradient


# ---------------------------------------------------------------- tree utils
def flatten_tensors(obj) -> Tuple[List[Tensor], Any]:
    """Flatten nested (list/tuple/dict) structure, extracting Tensor leaves."""
    tensors: List[Tensor] = []

    def rec(o):
        if isinstance(o, Tensor):
            tensors.append(o)
            return ("__T__", len(tensors) - 1)
        if isinstance(o, (list, tuple)):
            return (type(o).__name__, [rec(x) for x in o])
        if isinstance(o, dict):
            return ("dict", {k: rec(v) for k, v in o.items()})
        return ("leaf", o)

    spec = rec(obj)
    return tensors, spec


def unflatten_tensors(spec, tensors: List):
    kind, payload = spec
    if kind == "__T__":
        return tensors[payload]
    if kind == "list":
        return [unflatten_tensors(s, tensors) for s in payload]
    if kind == "tuple":
        return tuple(unflatten_tensors(s, tensors) for s in payload)
    if kind == "dict":
        return {k: unflatten_tensors(v, tensors) for k, v in payload.items()}
    return payload


def _spec_signature(spec) -> Any:
    """Hashable structural signature of a flatten spec."""
    kind, payload = spec
    if kind == "__T__":
        return ("T", payload)
    if kind in ("list", "tuple"):
        return (kind, tuple(_spec_signature(s) for s in payload))
    if kind == "dict":
        return ("dict", tuple(sorted((k, _spec_signature(v)) for k, v in payload.items())))
    try:
        hash(payload)
        return ("leaf", payload)
    except TypeError:
        return ("leaf", repr(payload))


class _SwapValues:
    """Temporarily swap Tensor payloads for tracers during tracing."""

    def __init__(self, tensors: List[Tensor], values):
        self.tensors = tensors
        self.values = values

    def __enter__(self):
        self.saved = [t._value for t in self.tensors]
        for t, v in zip(self.tensors, self.values):
            t._value = v
        return self

    def __exit__(self, *exc):
        for t, v in zip(self.tensors, self.saved):
            t._value = v
        return False


class StaticFunction:
    def __init__(self, function: Callable, input_spec=None, build_strategy=None, backend=None,
                 full_graph=False, donate_state=False, bucket_dynamic_batch=False,
                 state_layer=None):
        from ..nn.layer.layers import Layer

        # state_layer: trace this Layer's params/buffers as state even though
        # ``function`` is a plain callable (closures over a model, e.g. the
        # compiled decode loop in models/generation.py)
        self._layer: Optional[Layer] = state_layer
        if isinstance(function, Layer):
            self._layer = function
            self._fn = function.forward
        elif hasattr(function, "__self__") and isinstance(getattr(function, "__self__", None), Layer):
            self._layer = function.__self__
            self._fn = function
        else:
            self._fn = function
        self._input_spec = input_spec
        self._bucket_dynamic_batch = bucket_dynamic_batch
        self._cache: Dict[Any, Any] = {}
        # guard keys whose trace failed: calls fall back to eager (the SOT
        # graph-break analog, reference opcode_executor.py:1594 resume-eager)
        self._fallback_keys: set = set()
        self._full_graph = full_graph
        self._warned_fallback = False
        functools.update_wrapper(self, function if callable(function) else self._fn)

    # paddle surface
    @property
    def concrete_program(self):
        return None

    def _state_tensors(self) -> List[Tensor]:
        if self._layer is None:
            return []
        out = list(self._layer.parameters())
        out += [b for b in self._layer.buffers() if b is not None]
        return out

    def _guards(self, arg_tensors, spec, training):
        from ..amp.auto_cast import amp_state

        st = amp_state()
        return (
            _spec_signature(spec),
            tuple((tuple(t._value.shape), str(t._value.dtype), t.stop_gradient) for t in arg_tensors),
            training,
            (st.enabled, st.dtype, st.level),
            tape.grad_enabled(),
        )

    def _build(self, spec, n_state, n_args, training):
        fn = self._fn
        state_tensors = self._state_tensors()
        meta = {}

        def functional(rng_key, flat_vals):
            state_vals = flat_vals[:n_state]
            arg_vals = flat_vals[n_state:]
            ctx = trace_state.TraceContext(rng_key)
            arg_tensors = [Tensor(v, stop_gradient=False) for v in arg_vals]
            with trace_state.activate(ctx), _SwapValues(state_tensors, state_vals):
                args, kwargs = unflatten_tensors(spec, arg_tensors)
                with tape.no_grad():
                    out = fn(*args, **kwargs)
                out_tensors, out_spec = flatten_tensors(out)
                meta["out_spec"] = out_spec
                meta["updated_buffers"] = [b for b, _ in ctx.buffer_updates]
                buf_vals = tuple(v for _, v in ctx.buffer_updates)
                return tuple(t._value for t in out_tensors) + buf_vals

        jit_fwd = jax.jit(functional)

        def fwd_bwd(rng_key, flat_vals, cotangents):
            outs, vjp_fn = jax.vjp(lambda fv: functional(rng_key, fv), list(flat_vals))
            (grads,) = vjp_fn(cotangents)
            return grads

        jit_bwd = jax.jit(fwd_bwd)
        return {"fwd": jit_fwd, "bwd": jit_bwd, "meta": meta}

    # -------------------------------------------- dynamic-dim bucket policy
    def _dynamic_batch_dims(self):
        """Arg indices whose InputSpec marks dim 0 dynamic (None/-1).

        Policy for SURVEY §7.3's dynamic-shape hard part: with
        ``bucket_dynamic_batch=True`` the batch dim is zero-padded to the
        next power of two and batch-mapped outputs sliced back, bounding the
        compile cache to O(log max_batch) entries instead of one per batch
        size. OPT-IN because padding asserts batch-row independence: models
        with cross-batch coupling (train-mode BatchNorm, in-graph
        mean-over-batch losses) would see the zero rows, and every output
        whose LEADING dim equals the padded batch is treated as batch-major
        and sliced (an aux output that coincidentally matches is truncated).
        Without the flag, dynamic dims compile per exact shape — always
        correct."""
        if not self._input_spec or not self._bucket_dynamic_batch:
            return None
        dyn = []
        for i, s in enumerate(self._input_spec):
            if isinstance(s, InputSpec) and s.shape and s.shape[0] in (None, -1):
                dyn.append(i)
        return dyn or None

    @staticmethod
    def _bucket(n: int) -> int:
        b = 1
        while b < n:
            b <<= 1
        return b

    def _run_segmented(self, args, kwargs):
        """Graph-break execution: record ops lazily, compile one segment per
        host-read boundary (jit.lazy_segments)."""
        from . import lazy_segments
        from .hlo_dump import dump_dir

        name = getattr(self._fn, "__name__", "fn")
        out, nseg = lazy_segments.run_segmented(
            self._fn, args, kwargs, name=name,
            dump_name=f"to_static_{name}" if dump_dir() else None)
        self.last_segment_count = nseg
        return out

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            return self._fn(*args, **kwargs)  # jit.enable_to_static(False)
        from ..ops import dispatch as _dispatch

        if _dispatch._lazy_ctx is not None:
            # called from inside a segmented (graph-broken) outer function:
            # inline — our ops record into the OUTER segment; invoking the
            # compiled entry would hand it pending abstract values
            return self._fn(*args, **kwargs)
        training = self._layer.training if self._layer is not None else True
        arg_tensors, spec = flatten_tensors((args, kwargs))

        dyn = self._dynamic_batch_dims()
        real_n = None
        if dyn and not kwargs and len(args) >= len(self._input_spec):
            real_n = int(arg_tensors[dyn[0]]._value.shape[0])
            bucket = self._bucket(real_n)
            if bucket != real_n:
                padded = []
                for i, t in enumerate(arg_tensors):
                    if i in dyn:
                        v = t._value
                        pad = [(0, bucket - v.shape[0])] + [(0, 0)] * (v.ndim - 1)
                        pt = Tensor(jnp.pad(v, pad), stop_gradient=t.stop_gradient)
                        padded.append(pt)
                    else:
                        padded.append(t)
                arg_tensors = padded
            else:
                real_n = None  # exact bucket: nothing to slice back

        state_tensors = self._state_tensors()
        key = self._guards(arg_tensors, spec, training)
        if key in self._fallback_keys:
            return self._run_segmented(args, kwargs)  # cached graph-break
        entry = self._cache.get(key)
        n_state = len(state_tensors)
        new_entry = entry is None
        if new_entry:
            entry = self._build(spec, n_state, len(arg_tensors), training)
            self._cache[key] = entry
        all_tensors = state_tensors + arg_tensors
        flat_vals = tuple(t._value for t in all_tensors)
        rng_key = default_generator().next_key()

        if new_entry:
            from .hlo_dump import dump_dir, maybe_dump

            if dump_dir():
                maybe_dump(f"to_static_{getattr(self._fn, '__name__', 'fn')}",
                           entry["fwd"], (rng_key, flat_vals))
        try:
            raw_outs = entry["fwd"](rng_key, flat_vals)
        except _TRACE_BREAK_ERRORS as e:
            # graph break: the function does data-dependent Python (e.g.
            # .numpy()/bool() on a traced value). Switch this specialization
            # to SEGMENTED execution — ops before each host read compile as
            # one program, the read runs on the materialized value, and the
            # ops after form the next compiled segment (the SOT
            # split-at-the-failing-op contract, opcode_executor.py:1594,
            # without a bytecode interpreter). full_graph=True keeps the
            # reference's strict mode.
            if self._full_graph:
                raise
            self._fallback_keys.add(key)
            self._cache.pop(key, None)
            if not self._warned_fallback:
                self._warned_fallback = True
                import warnings

                name = getattr(self._fn, "__name__", "fn")
                warnings.warn(
                    f"to_static({name}): graph break "
                    f"({type(e).__name__}); splitting this input signature "
                    "into compiled segments at host reads. Pass "
                    "full_graph=True to error instead.")
            return self._run_segmented(args, kwargs)
        meta = entry["meta"]
        out_spec = meta["out_spec"]
        updated_buffers = meta["updated_buffers"]
        n_real = len(raw_outs) - len(updated_buffers)

        # write back buffer updates (concrete device arrays)
        for b, v in zip(updated_buffers, raw_outs[n_real:]):
            b._value = v

        needs_grad = tape.grad_enabled() and any(not t.stop_gradient for t in all_tensors)
        out_vals = list(raw_outs[:n_real])
        if needs_grad:
            jit_bwd = entry["bwd"]
            n_outs_total = len(raw_outs)
            out_metas = [jax.ShapeDtypeStruct(jnp.shape(o), jnp.result_type(o)) for o in raw_outs]

            def vjp_fn(cots):
                cot_seq = list(cots) if isinstance(cots, tuple) else [cots]
                # pad zero cotangents for the buffer-update outputs
                cot_full = tuple(cot_seq) + tuple(
                    jnp.zeros(m.shape, m.dtype) for m in out_metas[n_real:]
                )
                grads = jit_bwd(rng_key, flat_vals, cot_full)
                return tuple(grads)

            def primal_fn(*vals, _fwd=entry["fwd"], _key=rng_key, _n=n_real):
                return list(_fwd(_key, list(vals))[:_n])

            node = tape.GradNode(vjp_fn, all_tensors, out_vals, name="to_static",
                                 fn=primal_fn)
            out_tensors = []
            for i, v in enumerate(out_vals):
                t = Tensor(v, stop_gradient=False)
                t._grad_node = node
                t._out_index = i
                out_tensors.append(t)
        else:
            out_tensors = [Tensor(v, stop_gradient=True) for v in out_vals]
        if real_n is not None:
            # slice padded batch rows back off every output that carries
            # them — through the tape, so cotangents zero-pad on backward
            from ..ops.dispatch import apply as _apply

            bucket = arg_tensors[dyn[0]]._value.shape[0]
            out_tensors = [
                _apply(lambda v, _n=real_n: v[:_n], t, op_name="unbucket_slice")
                if t._value.ndim >= 1 and t._value.shape[0] == bucket else t
                for t in out_tensors
            ]
        return unflatten_tensors(out_spec, out_tensors)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """Decorator/wrapper parity with paddle.jit.to_static.

    ``full_graph=False`` (default, matching the reference's SOT mode) falls
    back to eager per input-signature on untraceable Python (graph break);
    ``full_graph=True`` raises instead (the reference's strict AST mode)."""

    def decorate(fn):
        return StaticFunction(fn, input_spec=input_spec, build_strategy=build_strategy,
                              backend=backend,
                              full_graph=kwargs.get("full_graph", False),
                              bucket_dynamic_batch=kwargs.get("bucket_dynamic_batch", False),
                              state_layer=kwargs.get("state_layer"))

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn=None):
    if fn is None:
        return lambda f: f
    return fn


def ignore_module(modules):
    return None


class TrainStep:
    """Whole-training-step compilation — the TPU-idiomatic hot path.

    Compiles loss_fn(model(x), y) + grads + THE FRAMEWORK'S OWN optimizer
    update (``Optimizer._update_param`` for all ten optimizers, param groups,
    grad clip, ``multi_precision`` fp32 master weights) into ONE XLA program
    with donated parameter/optimizer buffers.  The optimizer's accumulators
    are materialized up front (``_ensure_state``) and threaded through the
    compiled step as a pytree, so eager ``state_dict()``/checkpointing always
    sees the live state.  LR schedulers are evaluated host-side per call and
    enter the graph as a traced scalar.  Pass a ``paddle_tpu.amp.GradScaler``
    to get fp16-style dynamic loss scaling with the found-inf skip executed
    *inside* the compiled step (no per-step host sync).

    Reference anchor: python/paddle/optimizer/optimizer.py:125 (_create_
    accumulators / master-weight semantics), amp/grad_scaler.py.
    """

    def __init__(self, model, loss_fn, optimizer, donate: bool = True, scaler=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.scaler = scaler if (scaler is not None and scaler.is_enable()) else None
        self._params = list(model.parameters())
        self._buffers = [b for b in model.buffers() if b is not None]
        with SetupSpan("train_step.init") as span:
            optimizer._ensure_state()
            self._pid2idx = {id(p): i for i, p in enumerate(self._params)}
            self._commit_state_to_mesh()
            span.note(bytes=sum(int(a.nbytes) for a in
                                jax.tree_util.tree_leaves(self._get_opt_state())))
        self._compiled = None
        # until a call has compiled nothing: a call that grows the step's
        # cache leaves a ``program.acquire`` row (the second call may too,
        # where the first one's outputs are placed as its inputs were not)
        self._acquiring = True
        self._multi_cache: Dict[Any, Any] = {}
        self._step_raw = None
        self._donate = donate

    def _commit_state_to_mesh(self):
        """Under a fleet mesh, state that no layer placed (norm weights, rope
        buffers, optimizer scalars) sits on one device, and the step returns
        it committed to the mesh: the second call would see new input
        shardings and compile the whole step again (measured on four v5e
        chips: 81 s, then 75 s). Replicating it up front gives the first call
        the shardings every later call has."""
        from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

        from ..distributed.topology import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        if hcg is None or hcg.mesh.size == 1 or jax.process_count() > 1:
            return
        replicated = NamedSharding(hcg.mesh, PartitionSpec())

        def put(v):
            if isinstance(getattr(v, "sharding", None), SingleDeviceSharding):
                return jax.device_put(v, replicated)
            return v

        for t in self._params + self._buffers:
            t._value = put(t._value)
        accs, masters = self._get_opt_state()
        self._put_opt_state(jax.tree_util.tree_map(put, accs),
                            jax.tree_util.tree_map(put, masters))

    # -------------------------------------------------- state pytree helpers
    def _get_opt_state(self):
        opt = self.optimizer
        accs = {
            name: {self._pid2idx[pid]: v for pid, v in d.items() if pid in self._pid2idx}
            for name, d in opt._accumulators.items()
        }
        masters = {self._pid2idx[pid]: v
                   for pid, v in opt._master_weights.items() if pid in self._pid2idx}
        return accs, masters

    def _put_opt_state(self, accs, masters):
        opt = self.optimizer
        for name, d in accs.items():
            for i, v in d.items():
                opt._accumulators[name][id(self._params[i])] = v
        for i, v in masters.items():
            opt._master_weights[id(self._params[i])] = v

    def _scaler_state(self):
        s = self.scaler
        if s is None:
            return {}
        return {
            "scale": jnp.asarray(s._scale, jnp.float32),
            "good": jnp.asarray(s._good_steps, jnp.int32),
            "bad": jnp.asarray(s._bad_steps, jnp.int32),
        }

    # ------------------------------------------------------------- build
    def _build(self, batch_spec):
        model = self.model
        loss_fn = self.loss_fn
        buffers = self._buffers
        params = self._params
        opt = self.optimizer
        scaler = self.scaler

        def step(param_vals, accs, masters, buf_vals, scaler_state, rng_key, batch_vals, lr):
            # ---- forward + grads (scaled loss when a GradScaler is active)
            def loss_of(pv):
                ctx = trace_state.TraceContext(rng_key)
                batch_tensors = [Tensor(v, stop_gradient=True) for v in batch_vals]
                with trace_state.activate(ctx), _SwapValues(params, pv), _SwapValues(buffers, buf_vals):
                    with tape.no_grad():
                        args = unflatten_tensors(batch_spec, batch_tensors)
                        loss = loss_fn(model, *args)
                    new_bufs = {id(b): v for b, v in ctx.buffer_updates}
                    buf_out = [new_bufs.get(id(b), bv) for b, bv in zip(buffers, buf_vals)]
                lv = loss._value
                scaled = lv * scaler_state["scale"].astype(lv.dtype) if scaler else lv
                return scaled, (lv, buf_out)

            (_, (loss_val, buf_out)), grads = jax.value_and_grad(loss_of, has_aux=True)(
                list(param_vals)
            )

            found_inf = None
            if scaler:
                with jax.named_scope("grad_unscale"):
                    inv = (1.0 / scaler_state["scale"])
                    grads = [g * inv.astype(g.dtype) for g in grads]
                    nonfinite = sum(jnp.sum(~jnp.isfinite(g)) for g in grads)
                    found_inf = nonfinite > 0

            # ZeRO stage >= 2: constrain grads to the sharding axis so XLA
            # emits reduce-scatter instead of all-reduce (auto_parallel
            # ShardingStage2/3.shard_grad)
            shard_grad = getattr(opt, "_shard_grad", None)
            if shard_grad is not None:
                grads = [shard_grad(p, g) for p, g in zip(params, grads)]

            # ---- optimizer update: trace the framework's own _update_param.
            # Install traced state into the optimizer's dicts for the duration
            # of the trace, then restore the concrete values.
            saved_accs = {name: dict(d) for name, d in opt._accumulators.items()}
            saved_masters = dict(opt._master_weights)
            self._put_opt_state(accs, masters)
            grad_of = {id(p): g for p, g in zip(params, grads)}
            try:
                with _SwapValues(params, list(param_vals)), jax.named_scope("optimizer"):
                    for group in opt._param_groups:
                        pg = [
                            (p, Tensor(grad_of[id(p)], stop_gradient=True))
                            for p in group["params"]
                            if id(p) in grad_of and p.trainable
                        ]
                        if opt._grad_clip is not None:
                            pg = opt._grad_clip(pg)
                        glr = lr * group.get("learning_rate", 1.0)
                        wd = group.get("weight_decay", opt._weight_decay)
                        wd = opt._parse_decay(wd) if not isinstance(wd, float) else wd
                        with tape.no_grad():
                            for p, g in pg:
                                gv = (
                                    g._value.astype(jnp.float32)
                                    if opt._multi_precision
                                    else g._value
                                )
                                opt._update_param(p, gv, glr, wd)
                    new_params = [p._value for p in params]
                new_accs = {
                    name: {i: opt._accumulators[name][id(params[i])] for i in accs[name]}
                    for name in accs
                }
                new_masters = {i: opt._master_weights[id(params[i])] for i in masters}
            finally:
                opt._accumulators.clear()
                opt._accumulators.update(
                    {name: dict(d) for name, d in saved_accs.items()}
                )
                opt._master_weights.clear()
                opt._master_weights.update(saved_masters)

            new_scaler_state = scaler_state
            if scaler:
                # skip the whole update when any grad is nonfinite
                keep = lambda new, old: jnp.where(found_inf, old, new)  # noqa: E731
                new_params = [keep(n, o) for n, o in zip(new_params, param_vals)]
                new_accs = jax.tree_util.tree_map(keep, new_accs, accs)
                new_masters = jax.tree_util.tree_map(keep, new_masters, masters)
                if scaler._dynamic:
                    scale = scaler_state["scale"]
                    bad = jnp.where(found_inf, scaler_state["bad"] + 1, 0)
                    good = jnp.where(found_inf, 0, scaler_state["good"] + 1)
                    dec = bad >= scaler._decr_every_n
                    scale = jnp.where(dec, jnp.maximum(scale * scaler._decr_ratio, 1.0), scale)
                    bad = jnp.where(dec, 0, bad)
                    inc = good >= scaler._incr_every_n_steps
                    scale = jnp.where(inc, scale * scaler._incr_ratio, scale)
                    good = jnp.where(inc, 0, good)
                    new_scaler_state = {"scale": scale, "good": good, "bad": bad}

            return loss_val, new_params, new_accs, new_masters, buf_out, new_scaler_state

        donate = (0, 1, 2, 3) if self._donate else ()
        self._step_raw = step
        return jax.jit(step, donate_argnums=donate)

    # ------------------------------------------------------------- call
    def __call__(self, *batch):
        with RecordEvent("train_step.call", step=self.optimizer._step_count,
                         steps=1):
            batch_tensors, spec = flatten_tensors(batch)
            first_call = self._compiled is None
            if first_call:
                self._spec = spec
                self._spec_sig = _spec_signature(spec)
                self._compiled = self._build(spec)
            elif _spec_signature(spec) != self._spec_sig:
                raise ValueError(
                    "TrainStep is specialized to the batch structure of its first "
                    "call; build a new TrainStep for a different structure")
            batch_vals = tuple(t._value for t in batch_tensors)
            rng_key = default_generator().next_key()
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            buf_vals = [b._value for b in self._buffers]
            accs, masters = self._get_opt_state()
            if first_call:
                from .hlo_dump import dump_dir, maybe_dump

                if dump_dir():
                    maybe_dump("train_step", self._compiled,
                               ([p._value for p in self._params], accs, masters, buf_vals,
                                self._scaler_state(), rng_key, batch_vals, lr))
            if self._acquiring:
                had, t0 = self._compiled._cache_size(), SETUP.clock()
            loss, new_params, new_accs, new_masters, buf_out, new_scaler = self._compiled(
                [p._value for p in self._params], accs, masters, buf_vals,
                self._scaler_state(), rng_key, batch_vals, lr,
            )
            if self._acquiring:
                self._acquiring = self._compiled._cache_size() > had
                if self._acquiring:
                    SETUP.acquired(self._compiled.__name__, SETUP.clock() - t0, since=t0,
                                   kind="train", k=1)
            for p, v in zip(self._params, new_params):
                p._value = v
            self._put_opt_state(new_accs, new_masters)
            for b, v in zip(self._buffers, buf_out):
                b._value = v
            if self.scaler is not None and new_scaler:
                self.scaler._scale = new_scaler["scale"]
                self.scaler._good_steps = new_scaler["good"]
                self.scaler._bad_steps = new_scaler["bad"]
            self.optimizer._step_count += 1
            return Tensor(loss)

    def sync_to_model(self):
        """Params are written back after every step; kept for API compat."""
        return self.model

    # ------------------------------------------------------- multi-step scan
    def run_steps(self, *batch_stacks):
        """Run K optimizer steps in ONE compiled dispatch.

        Each tensor leaf in ``batch_stacks`` carries a leading dim K (one
        slice per step); the whole schedule executes as a ``lax.scan`` over
        that dim, so per-dispatch host/marshalling overhead is paid once per
        K steps instead of per step (decisive for models with many small
        parameter tensors). Returns the
        per-step losses as a [K] tensor. The learning rate is evaluated once
        and held constant across the window (scheduler advances by K after).
        """
        batch_tensors, spec = flatten_tensors(batch_stacks)
        if not batch_tensors:
            raise ValueError("run_steps needs at least one tensor input")
        K = int(batch_tensors[0]._value.shape[0])
        spec_sig = _spec_signature(spec)
        if self._compiled is None:
            # build the single-step program for this batch structure (the
            # stacked spec has the same TREE as the per-step spec)
            self._spec = spec
            self._spec_sig = spec_sig
            self._compiled = self._build(spec)
        elif spec_sig != self._spec_sig:
            raise ValueError(
                "TrainStep is specialized to the batch structure of its first "
                "call; build a new TrainStep for a different structure")
        with RecordEvent("train_step.call", step=self.optimizer._step_count,
                         steps=K):
            multi = self._multi_cache.get(spec_sig)
            first_call = multi is None
            if first_call:
                step_raw = self._step_raw

                def multi_fn(param_vals, accs, masters, buf_vals, scaler_state,
                             base_key, batch_stack_vals, lr):
                    # K comes from the stack itself (jit retraces per shape), so
                    # the structure-keyed cache serves any window length
                    n_steps = batch_stack_vals[0].shape[0]

                    def body(carry, xs):
                        pv, ac, ms, bv, ss = carry
                        i, batch_vals = xs
                        key = jax.random.fold_in(base_key, i)
                        loss, pv, ac, ms, bv, ss = step_raw(
                            pv, ac, ms, bv, ss, key, batch_vals, lr)
                        return (pv, ac, ms, bv, ss), loss

                    carry0 = (list(param_vals), accs, masters, list(buf_vals),
                              scaler_state)
                    (pv, ac, ms, bv, ss), losses = jax.lax.scan(
                        body, carry0, (jnp.arange(n_steps), tuple(batch_stack_vals)))
                    return losses, pv, ac, ms, bv, ss

                donate = (0, 1, 2, 3) if self._donate else ()
                multi = jax.jit(multi_fn, donate_argnums=donate)
                self._multi_cache[spec_sig] = multi

            batch_vals = tuple(t._value for t in batch_tensors)
            base_key = default_generator().next_key()
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            accs, masters = self._get_opt_state()
            t0 = SETUP.clock() if first_call else None
            losses, new_params, new_accs, new_masters, buf_out, new_scaler = multi(
                [p._value for p in self._params], accs, masters,
                [b._value for b in self._buffers], self._scaler_state(),
                base_key, batch_vals, lr,
            )
            if first_call:
                SETUP.acquired(multi.__name__, SETUP.clock() - t0, since=t0, kind="train", k=K)
            for p, v in zip(self._params, new_params):
                p._value = v
            self._put_opt_state(new_accs, new_masters)
            for b, v in zip(self._buffers, buf_out):
                b._value = v
            if self.scaler is not None and new_scaler:
                self.scaler._scale = new_scaler["scale"]
                self.scaler._good_steps = new_scaler["good"]
                self.scaler._bad_steps = new_scaler["bad"]
            self.optimizer._step_count += K
            return Tensor(losses)
