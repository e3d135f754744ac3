"""nn.Layer — the module base class.

Mirrors the reference's Layer (reference: python/paddle/nn/layer/layers.py —
unverified, SURVEY.md §0): parameter/sublayer registration via __setattr__,
hooks, state_dict with structured names, train/eval mode, apply/to. All
parameter storage is paddle_tpu Tensors; the functional bridge
(``paddle_tpu.jit.functional_call``) swaps their values for jit'd training.
"""
from __future__ import annotations

import re
from collections import OrderedDict

import numpy as np

from ...core.tensor import Tensor, Parameter
from ...core.dtype import get_default_dtype, to_jax_dtype
from ...core import autograd
from .. import initializer as init_mod

__all__ = ["Layer", "ParamAttr"]


class ParamAttr:
    """paddle.ParamAttr (reference: python/paddle/base/param_attr.py)."""

    def __init__(
        self,
        name=None,
        initializer=None,
        learning_rate=1.0,
        regularizer=None,
        trainable=True,
        do_model_average=True,
        need_clip=True,
    ):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip


_layer_counters: dict[str, int] = {}


def _unique_name(prefix: str) -> str:
    idx = _layer_counters.get(prefix, 0)
    _layer_counters[prefix] = idx + 1
    return f"{prefix}_{idx}"


class HookRemoveHelper:
    def __init__(self, hooks, key):
        self._hooks, self._key = hooks, key

    def remove(self):
        self._hooks.pop(self._key, None)


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        self.training = True
        self._dtype = dtype or get_default_dtype()
        self._full_name = _unique_name(
            name_scope or re.sub(r"(?<!^)(?=[A-Z])", "_", type(self).__name__).lower()
        )
        self._parameters: OrderedDict[str, Parameter] = OrderedDict()
        self._sub_layers: OrderedDict[str, Layer] = OrderedDict()
        self._buffers: OrderedDict[str, Tensor] = OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks: OrderedDict[int, object] = OrderedDict()
        self._forward_post_hooks: OrderedDict[int, object] = OrderedDict()
        self._hook_id = 0

    # -- registration --------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning params")
            params[name] = value
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() before assigning layers")
            layers[name] = value
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                params.pop(name)
                object.__setattr__(self, name, None)
            elif isinstance(value, Tensor):
                params[name].set_value(value)
            else:
                raise TypeError(f"cannot assign {type(value)} to parameter {name}")
        elif buffers is not None and name in buffers:
            if value is None:
                buffers.pop(name)
                object.__setattr__(self, name, None)
            else:
                buffers[name] = value if isinstance(value, Tensor) else Tensor(value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'"
        )

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = (
            list(self._parameters) + list(self._sub_layers) + list(self._buffers)
        )
        return sorted(set(list(super().__dir__()) + extra))

    def add_sublayer(self, name, sublayer):
        if not isinstance(sublayer, Layer) and sublayer is not None:
            raise TypeError("sublayer must be a Layer")
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("parameter must be a Parameter")
        self._parameters[str(name)] = parameter
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[str(name)] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(str(name))
        return tensor

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ):
        """Create + register-later parameter (caller assigns it)."""
        dtype = dtype or self._dtype
        if isinstance(attr, ParamAttr):
            initializer = attr.initializer
            trainable = attr.trainable
        elif attr is False:
            return None
        else:
            initializer, trainable = None, True
        if initializer is None:
            # ``set_global_initializer`` (paddle's semantics): over a
            # layer's default, never over a ParamAttr's own
            initializer = (init_mod._global_bias_init if is_bias
                           else init_mod._global_weight_init
                           ) or default_initializer
        if initializer is None:
            if is_bias:
                initializer = init_mod.Constant(0.0)
            else:
                initializer = init_mod.XavierNormal()
        value = initializer(shape, to_jax_dtype(dtype))
        p = Parameter(value, dtype=dtype, trainable=trainable)
        # deterministic paddle-style name (linear_0.w_0) so optimizer
        # checkpoints keyed by name survive process restarts
        idx = self.__dict__.setdefault("_param_name_counter", 0)
        self.__dict__["_param_name_counter"] = idx + 1
        p.name = f"{self._full_name}.{'b' if is_bias else 'w'}_{idx}"
        if isinstance(attr, ParamAttr):
            p._param_attr = attr
            if attr.name:
                p.name = attr.name
            p.optimize_attr = {"learning_rate": attr.learning_rate}
            p.regularizer = attr.regularizer
            p.need_clip = attr.need_clip
        else:
            p.optimize_attr = {"learning_rate": 1.0}
            p.regularizer = None
            p.need_clip = True
        p.is_bias = is_bias
        return p

    def create_variable(self, name=None, persistable=False, dtype=None):
        import jax.numpy as jnp

        t = Tensor(jnp.zeros((), to_jax_dtype(dtype or self._dtype)))
        t.persistable = persistable
        return t

    # -- traversal -----------------------------------------------------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self._traverse(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield (f"{name}.{pname}" if name else pname), p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self._traverse(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    yield (f"{name}.{bname}" if name else bname), b

    def _traverse(self, prefix="", include_sublayers=True):
        yield prefix, self
        if include_sublayers:
            for name, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sub_prefix = f"{prefix}.{name}" if prefix else name
                yield from sub._traverse(sub_prefix, True)

    def children(self):
        for _, sub in self.named_children():
            yield sub

    def named_children(self):
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def sublayers(self, include_self=False):
        out = []
        for name, layer in self._traverse("", True):
            if layer is self and not include_self:
                continue
            out.append(layer)
        return out

    def named_sublayers(self, prefix="", include_self=False):
        for name, layer in self._traverse(prefix, True):
            if layer is self and not include_self:
                continue
            yield name, layer

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def full_name(self):
        return self._full_name

    # -- mode ----------------------------------------------------------------
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    # -- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, use_hook=True):
        out = OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            out[name] = p
        for name, layer in self._traverse("", include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None and bname not in layer._non_persistable_buffer_names:
                    key = f"{name}.{bname}" if name else bname
                    out[key] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for key, value in state_dict.items():
            if key not in own:
                unexpected.append(key)
                continue
            target = own[key]
            v = value.numpy() if isinstance(value, Tensor) else np.asarray(value)
            if tuple(v.shape) != tuple(target.shape):
                raise ValueError(
                    f"shape mismatch for {key}: loaded {v.shape} vs "
                    f"param {tuple(target.shape)}"
                )
            target.set_value(v)
        for key in own:
            if key not in state_dict:
                missing.append(key)
        return missing, unexpected

    load_dict = set_state_dict

    # -- conversion ----------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._transform_dtype(dtype)
        return self

    def astype(self, dtype):
        self._transform_dtype(dtype)
        return self

    def _transform_dtype(self, dtype):
        import jax.numpy as jnp

        jdt = to_jax_dtype(dtype)
        for _, p in self.named_parameters():
            if jnp.issubdtype(p._value.dtype, jnp.floating):
                p._value = p._value.astype(jdt)
        for _, b in self.named_buffers():
            if jnp.issubdtype(b._value.dtype, jnp.floating):
                b._value = b._value.astype(jdt)
        for layer in self.sublayers(include_self=True):
            layer._dtype = str(jdt)

    def float(self):
        return self.astype("float32")

    def half(self):
        return self.astype("float16")

    def bfloat16(self):
        return self.astype("bfloat16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ----------------------------------------------------------------
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

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = [sub_repr[0]] + ["  " + ln for ln in sub_repr[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub_repr))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"
