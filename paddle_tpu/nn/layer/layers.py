"""nn.Layer — module base (reference: python/paddle/nn/layer/layers.py).

Same contract as the reference Layer (parameters/buffers/sublayers registries,
hooks, state_dict, train/eval) with one TPU-first addition: `functional_call`,
which runs forward with parameters/buffers substituted from a flat dict. That
single method is the bridge from the imperative API to jax transforms — the
compiled train step, pjit sharding, and the auto-parallel engine all use it.
"""
from __future__ import annotations

import collections
import warnings

import jax
import numpy as np

from ...framework import dtype as dtypes
from ...framework.core import Parameter, Tensor, to_tensor
from ...observability import tracing as _tracing


class HookRemoveHelper:
    def __init__(self, hooks, key):
        self._hooks, self._key = hooks, key

    def remove(self):
        self._hooks.pop(self._key, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtypes.convert_dtype(dtype)
        self._parameters = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names_set = set()
        self._sub_layers = collections.OrderedDict()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0
        self._name_scope = name_scope or type(self).__name__.lower()

    # -- registration -------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning parameters")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() before assigning sublayers")
            layers[name] = value
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                del params[name]
                object.__setattr__(self, name, None)
            else:
                params[name] = value
        elif buffers is not None and name in buffers:
            buffers[name] = value if value is None or isinstance(value, Tensor) else to_tensor(value)
        elif layers is not None and name in layers:
            if value is None:
                del layers[name]
                object.__setattr__(self, name, None)
            else:
                layers[name] = value
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(self._buffers) + list(self._sub_layers)

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[str(name)] = parameter
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[str(name)] = tensor
        if not persistable:
            self._non_persistable_buffer_names_set.add(str(name))
        return tensor

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ):
        from .. import initializer as I

        dtype = dtypes.convert_dtype(dtype) or self._dtype
        init = default_initializer
        name = None
        learning_rate = 1.0
        if attr is not None and attr is not False:
            if isinstance(attr, I.Initializer):
                # reference accepts a bare Initializer as weight_attr/bias_attr
                # (ParamAttr._to_attr wraps it)
                init = attr
            else:
                init = getattr(attr, "initializer", None) or init
                name = getattr(attr, "name", None)
                learning_rate = getattr(attr, "learning_rate", 1.0)
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        # the set-up log's `setup.build`: every parameter of a burst of
        # creation in ONE record (an initialiser called again on a weight
        # that exists adds to its `init_calls`, not to `params`)
        with _tracing.setup_phase("setup.build", burst=True,
                                  synced=False) as part:
            data = init(tuple(int(s) for s in shape), dtype)
            p = Parameter(data, name=name)
            part.counts["params"] = 1
            part.counts["bytes"] = data.size * data.dtype.itemsize
        p.optimize_attr["learning_rate"] = learning_rate
        return p

    def create_tensor(self, name=None, persistable=None, dtype=None):
        import jax.numpy as jnp

        return Tensor(jnp.zeros((), dtypes.convert_dtype(dtype) or self._dtype))

    # -- traversal ----------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        layers_set = layers_set if layers_set is not None else set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = prefix + ("." if prefix else "") + name
            if id(sub) not in layers_set:
                layers_set.add(id(sub))
                yield p, sub
                yield from sub.named_sublayers(prefix=p, include_self=False, layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [s for _, s in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(s for s in self._sub_layers.values() if s is not None)

    def named_children(self):
        return iter((n, s) for n, s in self._sub_layers.items() if s is not None)

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for lname, layer in [("", self)] + (
            [(n, l) for n, l in self.named_sublayers()] if include_sublayers else []
        ):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                full = (prefix + "." if prefix else "") + (lname + "." if lname else "") + pname
                yield full, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for lname, layer in [("", self)] + (
            [(n, l) for n, l in self.named_sublayers()] if include_sublayers else []
        ):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                full = (prefix + "." if prefix else "") + (lname + "." if lname else "") + bname
                yield full, b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # -- modes --------------------------------------------------------------
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ---------------------------------------------------------------
    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            o = hook(self, inputs, outputs)
            if o is not None:
                outputs = o
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            body = repr(sub).split("\n")
            head = f"({name}): {body[0]}"
            lines.extend([head] + ["  " + b for b in body[1:]])
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n  " + "\n  ".join(lines) + "\n)"
        return main + ")"

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[structured_name_prefix + name] = p
        # persistability is per-OWNING-layer: consult each layer's own set
        seen = set()
        layers = [("", self)] + ([(n, l) for n, l in self.named_sublayers()] if include_sublayers else [])
        for lname, layer in layers:
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                if bname in layer._non_persistable_buffer_names_set:
                    continue
                full = (lname + "." if lname else "") + bname
                dest[structured_name_prefix + full] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True, strict=False):
        """Load `state_dict` into this layer's parameters/buffers.

        Key drift is never silent: non-empty missing/unexpected sets warn
        (checkpoint-format drift surfaces at LOAD time, not as mysteriously
        divergent training later), and strict=True upgrades the warning to
        a RuntimeError. Returns (missing, unexpected) as before.
        """
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                arr = v._data if isinstance(v, Tensor) else np.asarray(v)
                own[k].set_value(Tensor(arr))
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        if missing or unexpected:
            msg = (f"{type(self).__name__}.set_state_dict: "
                   f"{len(missing)} missing key(s) (stay at current init) "
                   f"{missing[:5]}{'...' if len(missing) > 5 else ''}, "
                   f"{len(unexpected)} unexpected key(s) (ignored) "
                   f"{unexpected[:5]}{'...' if len(unexpected) > 5 else ''}")
            if strict:
                raise RuntimeError(msg)
            warnings.warn(msg, stacklevel=2)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device motion ---------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._to_dtype(dtypes.convert_dtype(dtype))
        return self

    def astype(self, dtype):
        self._to_dtype(dtypes.convert_dtype(dtype))
        return self

    def _to_dtype(self, dt):
        # the set-up log's `setup.cast`. Not synced: each astype returns
        # before the device has run it, and the host goes on to trace
        # meanwhile; the wait is paid by the first program that runs
        with _tracing.setup_phase("setup.cast", synced=False) as phase:
            n = b_in = b_out = 0
            for layer in self.sublayers(include_self=True):
                layer._dtype = dt
                for held in (layer._parameters, layer._buffers):
                    for t in held.values():
                        if t is None or not dtypes.is_floating_point_dtype(
                                t.dtype):
                            continue
                        b_in += t._data.size * t._data.dtype.itemsize
                        t._data = t._data.astype(dt)
                        b_out += t._data.size * t._data.dtype.itemsize
                        n += 1
            phase.counts.update(arrays=n, bytes_in=b_in, bytes_out=b_out)

    def float(self):
        return self.astype(np.float32)

    def half(self):
        return self.astype(np.float16)

    def bfloat16(self):
        return self.astype(dtypes.bfloat16)

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # -- the functional bridge (TPU-first) ---------------------------------
    def functional_call(self, overrides, *inputs, training=None, **kwargs):
        """Run forward with parameters/buffers substituted from `overrides`
        (dict: state_dict name → Tensor/array). Restores originals after.

        This is how compiled paths trace the model: parameters become jit
        arguments, so XLA sees one pure function of (params, inputs).
        """
        handles = []  # (container, key, original)
        named = dict(self.named_parameters())
        named_buf = dict(self.named_buffers())

        def locate(name):
            parts = name.split(".")
            layer = self
            for p in parts[:-1]:
                layer = layer._sub_layers[p] if p in layer._sub_layers else getattr(layer, p)
            leaf = parts[-1]
            if leaf in layer._parameters:
                return layer._parameters, leaf
            if leaf in layer._buffers:
                return layer._buffers, leaf
            raise KeyError(name)

        prev_training = self.training
        try:
            for name, value in overrides.items():
                container, key = locate(name)
                orig = container[key]
                handles.append((container, key, orig))
                # substitute the EXACT object so the caller can read .grad
                # off it after backward (compiled train step contract)
                sub = value if isinstance(value, Tensor) else Tensor(value, stop_gradient=False)
                container[key] = sub
            if training is not None:
                for layer in self.sublayers(include_self=True):
                    layer.training = training
            return self(*inputs, **kwargs)
        finally:
            for container, key, orig in reversed(handles):
                container[key] = orig
            if training is not None:
                for layer in self.sublayers(include_self=True):
                    layer.training = prev_training

    def raw_state_dict(self):
        """state_dict as raw jax arrays (pytree-friendly)."""
        return {k: v._data for k, v in self.state_dict().items()}

    def load_raw_state_dict(self, raw):
        for k, v in raw.items():
            self.state_dict()[k].set_value(Tensor(v))
