"""Layer — the module base class.

Parity: /root/reference/python/paddle/nn/layer/layers.py:351 (paddle.nn.Layer):
parameter/sublayer registries via __setattr__, buffers, forward hooks,
state_dict/set_state_dict, train/eval, apply, to/astype.

TPU-native notes: parameters are eager Tensors (jax.Array payloads). The same
Layer object runs eagerly op-by-op or inside a jax.jit trace (to_static swaps
parameter values for tracers); sharded training annotates parameter values
with NamedSharding via paddle_tpu.distributed.shard_layer.
"""
from __future__ import annotations

import itertools
from collections import OrderedDict
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ...framework import dtype as dtype_mod
from ...tensor.tensor import Tensor

# depth of paddle.LazyGuard: parameters created inside it are abstract
_lazy_parameters = 0

__all__ = ["Layer"]

_layer_counter = itertools.count()


class HookRemoveHelper:
    def __init__(self, hooks: OrderedDict, hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        self._non_persistable_buffer_names_set = set()
        self.training = True
        self._dtype = dtype_mod.convert_dtype(dtype) if dtype is not None else dtype_mod.float32
        self._full_name = (name_scope or type(self).__name__.lower()) + f"_{next(_layer_counter)}"
        self._forward_pre_hooks: OrderedDict = OrderedDict()
        self._forward_post_hooks: OrderedDict = OrderedDict()
        self._hook_id = itertools.count()
        self._casted_by_pure_fp16 = False

    # ------------------------------------------------------------- registry
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        subs = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Tensor) and value.is_parameter:
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            for d in (subs, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if subs is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            subs[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params:
                if value is None:
                    params.pop(name)
                    object.__setattr__(self, name, value)
                    return
                params[name] = value
                return
            if buffers is not None and name in buffers:
                buffers[name] = value
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for reg in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(reg)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for reg in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(reg)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extras = []
        for reg in ("_parameters", "_sub_layers", "_buffers"):
            extras += list(self.__dict__.get(reg, {}))
        return list(super().__dir__()) + extras

    # --------------------------------------------------------- construction
    def create_parameter(
        self, shape, attr=None, dtype=None, is_bias=False, default_initializer=None,
    ) -> Tensor:
        """parity: layers.py create_parameter — resolves ParamAttr/initializer."""
        from ..initializer import Constant, XavierUniform
        from ...base.param_attr import ParamAttr

        dt = dtype_mod.convert_dtype(dtype) if dtype is not None else self._dtype
        init = None
        name = None
        trainable = True
        lr = 1.0
        if isinstance(attr, ParamAttr):
            init = attr.initializer
            name = attr.name
            trainable = attr.trainable
            lr = attr.learning_rate
        elif callable(attr) and attr is not None:
            init = attr
        if init is None:
            init = default_initializer
        if init is None:
            init = Constant(0.0) if is_bias else XavierUniform()
        if _lazy_parameters:       # inside paddle.LazyGuard: a shape and a type
            value = jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dt.np_dtype)
        else:
            value = init(tuple(int(s) for s in shape), dt.np_dtype)
        t = Tensor(value, stop_gradient=not trainable, name=name)
        t.is_parameter = True
        t.trainable = trainable
        t._optimize_attrs = {"learning_rate": lr}
        return t

    def add_parameter(self, name: str, parameter: Optional[Tensor]):
        if parameter is None:
            self._parameters[name] = None
        else:
            if not parameter.is_parameter:
                parameter.is_parameter = True
                parameter.stop_gradient = False
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names_set.add(name)
        return tensor

    # ------------------------------------------------------------ iteration
    def parameters(self, include_sublayers: bool = True) -> List[Tensor]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix: str = "", include_sublayers: bool = True) -> Iterator[Tuple[str, Tensor]]:
        seen = set()
        for name, layer, lp in self._walk(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{lp}.{pname}" if lp else pname), p

    def buffers(self, include_sublayers: bool = True) -> List[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        seen = set()
        for name, layer, lp in self._walk(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{lp}.{bname}" if lp else bname), b

    def _walk(self, prefix: str, include_sublayers: bool):
        """Yields (name, layer, dotted_prefix) depth-first."""
        yield ("", self, prefix)
        if include_sublayers:
            for sname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sub_prefix = f"{prefix}.{sname}" if prefix else sname
                yield from sub._walk(sub_prefix, True)

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        out = []
        for _, layer, _ in self._walk("", True):
            out.append(layer)
        return out if include_self else out[1:]

    def named_sublayers(self, prefix: str = "", include_self: bool = False):
        for i, (name, layer, lp) in enumerate(self._walk(prefix, True)):
            if i == 0 and not include_self:
                continue
            yield lp, layer

    # ------------------------------------------------------------- modes
    def train(self):
        self.training = True
        for sub in self.sublayers():
            sub.training = True
        return self

    def eval(self):
        self.training = False
        for sub in self.sublayers():
            sub.training = False
        return self

    def apply(self, fn: Callable[["Layer"], None]):
        for sub in self.sublayers(include_self=True):
            fn(sub)
        return self

    def full_name(self) -> str:
        return self._full_name

    # ------------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook):
        hid = next(self._hook_id)
        self._forward_pre_hooks[hid] = hook
        return HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = next(self._hook_id)
        self._forward_post_hooks[hid] = hook
        return HookRemoveHelper(self._forward_post_hooks, hid)

    # ------------------------------------------------------------- call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # ------------------------------------------------------------- state
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True) -> Dict[str, Tensor]:
        out = OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(prefix=structured_name_prefix, include_sublayers=include_sublayers):
            out[name] = p
        for _, layer, lp in self._walk(structured_name_prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names_set:
                    continue
                out[f"{lp}.{bname}" if lp else bname] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        """Returns (missing_keys, unexpected_keys) like the reference."""
        own = self.state_dict()
        missing, matched = [], set()
        for key, target in own.items():
            if key in state_dict:
                src = state_dict[key]
                val = src.numpy() if isinstance(src, Tensor) else np.asarray(src)
                if list(val.shape) != list(target.shape):
                    raise ValueError(
                        f"shape mismatch for {key}: checkpoint {list(val.shape)} vs model {list(target.shape)}"
                    )
                target.set_value(val.astype(target.dtype.np_dtype))
                matched.add(key)
            else:
                missing.append(key)
        unexpected = [k for k in state_dict if k not in own]
        return missing, unexpected

    load_dict = set_state_dict

    # ------------------------------------------------------------- dtype/device
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_to(dtype_mod.convert_dtype(dtype), include_non_float=False)
        return self

    def astype(self, dtype):
        self._cast_to(dtype_mod.convert_dtype(dtype), include_non_float=False)
        return self

    def _cast_to(self, dt: dtype_mod.DType, include_non_float: bool):
        for _, layer, _ in self._walk("", True):
            for name, p in list(layer._parameters.items()):
                if p is not None and (include_non_float or p.dtype.is_floating_point):
                    p._value = p._value.astype(dt.np_dtype)
            for name, b in list(layer._buffers.items()):
                if b is not None and (include_non_float or b.dtype.is_floating_point):
                    b._value = b._value.astype(dt.np_dtype)
        self._dtype = dt

    def float(self):
        return self.astype(dtype_mod.float32)

    def bfloat16(self):
        return self.astype(dtype_mod.bfloat16)

    def float16(self):
        return self.astype(dtype_mod.float16)

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for name, sub in self.named_children():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else type(self).__name__ + "()"
