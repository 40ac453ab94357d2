"""nn.Layer — module base class (ref python/paddle/fluid/dygraph/layers.py:76).

Keeps the reference surface (sublayers/parameters/state_dict/hooks/train-eval,
create_parameter with initializer attrs) while staying functional-transform
friendly: `functional_state` / `functional_call` expose the layer as a pure
function of (params, buffers, inputs) so jax.jit/grad/shard_map can consume it
(the performance path; see jit/compile.py).
"""
import collections
import contextlib

import numpy as np
import jax
import jax.numpy as jnp

from ..framework import state
from ..framework.tensor import Tensor, Parameter
from ..utils import telemetry
from . import initializer as I


class HookRemoveHelper:
    def __init__(self, hooks, key):
        self._hooks, self._key = hooks, key

    def remove(self):
        self._hooks.pop(self._key, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ------------------------------------------------------------ registration
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning params")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() before assigning layers")
            layers[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params:
                del params[name]
            if layers is not None and name in layers:
                del layers[name]
            if buffers is not None and name in buffers:
                if isinstance(value, Tensor):
                    buffers[name] = value
                    return
                del buffers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def add_parameter(self, name, parameter):
        self._parameters[str(name)] = parameter
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[str(name)] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(str(name))
        else:
            # static capture relies on this to thread the buffer through the
            # desc as a persist var instead of freezing it as a constant
            tensor.persistable = True
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """ref dygraph/layers.py create_parameter + ParamAttr handling."""
        from .param_attr import ParamAttr
        dtype = dtype or self._dtype or "float32"
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        init = None
        if attr is not None and attr.initializer is not None:
            init = attr.initializer
        elif default_initializer is not None:
            init = default_initializer
        elif is_bias:
            init = I.Constant(0.0)
        else:
            init = I.XavierNormal()
        # a constructor's parameters, one after another, are one
        # `param_init` entry of the process journal. Not waited for: what
        # the host's queue still holds is waited for by what comes next
        # (`to(dtype=)`'s span, `startup/cast`), and waiting here a
        # parameter cost 1.6 s of a 2.0 B-parameter constructor's 28
        with telemetry.startup_span("param_init") as span:
            data = init(shape, dtype)
            placed = isinstance(data, jax.Array) and not isinstance(
                data, jax.core.Tracer)
            span.ids.update(
                bytes=getattr(data, "nbytes", 0),
                on="device" if placed and any(
                    d.platform != "cpu" for d in data.devices()) else "host")
        p = Parameter(data, name=(attr.name if attr else None),
                      trainable=(attr.trainable if attr else True))
        if attr is not None:
            p.regularizer = attr.regularizer
            p.learning_rate = attr.learning_rate
        else:
            p.regularizer = None
            p.learning_rate = 1.0
        return p

    def create_tensor(self, name=None, persistable=False, dtype=None):
        return Tensor(jnp.zeros([], state.get_default_dtype()))

    # ------------------------------------------------------------ iteration
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else f"{prefix}.{name}"), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(prefix=sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(prefix=sub_prefix)

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for layer in self._sub_layers.values():
            if layer is not None:
                out.extend(layer.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True)

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # ------------------------------------------------------------ modes
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # ------------------------------------------------------------ hooks
    def register_forward_pre_hook(self, hook):
        key = len(self._forward_pre_hooks)
        self._forward_pre_hooks[key] = hook
        return HookRemoveHelper(self._forward_pre_hooks, key)

    def register_forward_post_hook(self, hook):
        key = len(self._forward_post_hooks)
        self._forward_post_hooks[key] = hook
        return HookRemoveHelper(self._forward_post_hooks, key)

    # ------------------------------------------------------------ call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            out = hook(self, inputs, outputs)
            if out is not None:
                outputs = out
        return outputs

    # ------------------------------------------------------------ state dict
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix=""):
        dest = destination if destination is not None else collections.OrderedDict()
        for n, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[n] = p
        for n, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            leaf = n.rsplit(".", 1)[-1]
            if leaf not in self._non_persistable_buffer_names:
                dest[n] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                own[k].set_value(arr.astype(own[k].numpy().dtype))
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            from ..framework.dtype import is_floating_point
            with telemetry.startup_span("cast") as span:
                cast = self.parameters() + [
                    b for b in self.buffers() if is_floating_point(b.dtype)]
                for t in cast:
                    t._data = t._data.astype(dtype)
                jax.block_until_ready([t._data for t in cast])
                span.ids["bytes"] = sum(t._data.nbytes for t in cast)
        return self

    def float(self):
        return self.to(dtype=jnp.float32)

    def bfloat16(self):
        return self.to(dtype=jnp.bfloat16)

    # ------------------------------------------------------------ functional
    def functional_state(self):
        """(params, buffers) as flat name->jnp.ndarray dicts, for jit'd steps."""
        params = {n: p._data for n, p in self.named_parameters()}
        buffers = {n: b._data for n, b in self.named_buffers()}
        return params, buffers

    @contextlib.contextmanager
    def _use_state(self, params=None, buffers=None):
        """Temporarily swap parameter/buffer arrays (used while tracing)."""
        saved_p, saved_b = {}, {}
        named_p = dict(self.named_parameters())
        named_b = dict(self.named_buffers())
        try:
            if params is not None:
                for n, arr in params.items():
                    saved_p[n] = named_p[n]._data
                    named_p[n]._data = arr
            if buffers is not None:
                for n, arr in buffers.items():
                    saved_b[n] = named_b[n]._data
                    named_b[n]._data = arr
            yield named_p, named_b
        finally:
            for n, arr in saved_p.items():
                named_p[n]._data = arr
            for n, arr in saved_b.items():
                named_b[n]._data = arr

    def functional_call(self, params, buffers, *inputs, method=None,
                        **kwargs):
        """Pure call: returns (outputs, new_buffers). Safe under jax tracing.
        `method` selects a non-forward entry point (e.g. GPT decode_step);
        only array-like positionals are Tensor-wrapped — pytrees (KV caches)
        and scalars pass through untouched."""
        def wrap(i):
            if isinstance(i, Tensor):
                return i
            if isinstance(i, (jax.Array, jax.core.Tracer, np.ndarray)):
                return Tensor(i)
            return i

        with state.functional_mode_ctx():
            with self._use_state(params, buffers) as (named_p, named_b):
                wrapped = [wrap(i) for i in inputs]
                for n in params:
                    named_p[n].stop_gradient = False
                fn = getattr(self, method) if method else self
                out = fn(*wrapped, **kwargs)
                new_buffers = {n: named_b[n]._data for n in (buffers or {})}
        return out, new_buffers

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split("\n")
            rep = [rep[0]] + ["  " + r for r in rep[1:]]
            lines.append(f"  ({name}): " + "\n".join(rep))
        main = self.__class__.__name__ + "(" + extra
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    def extra_repr(self):
        return ""


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __len__(self):
        return len(self._sub_layers)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        if idx < 0:
            idx += len(self)
        return self._sub_layers[str(idx)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and \
                len(layers[0]) and isinstance(layers[0][0], (list, tuple)):
            for name, l in layers[0]:
                self.add_sublayer(name, l)
        else:
            for i, l in enumerate(layers):
                if isinstance(l, tuple):
                    self.add_sublayer(l[0], l[1])
                else:
                    self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __len__(self):
        return len(self._parameters)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, p):
        self.add_parameter(str(len(self)), p)
        return self
