"""Weight initializers (reference: python/paddle/nn/initializer/).

Each initializer is a callable (shape, dtype) -> jax array drawing from the
global RNG discipline in framework.random.
"""
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import random as prandom
from ...framework.core import Tensor, to_tensor
from ...observability import tracing as _tracing


def _fans(shape):
    if len(shape) < 1:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


def _logged(call):
    """An initialiser's call as one part of the set-up log's `setup.build`
    burst: `init_calls` and the time inside it (`init_self_s`; the device
    may still be filling the array when it returns)."""

    @functools.wraps(call)
    def logged(self, shape, dtype):
        with _tracing.setup_phase("setup.build", burst=True) as part:
            out = call(self, shape, dtype)
            part.counts["init_calls"] = 1
            part.counts["init_self_s"] = (
                time.monotonic_ns() - part.t0_ns) / 1e9
        return out

    return logged


class Initializer:
    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "__call__" in cls.__dict__:
            cls.__call__ = _logged(cls.__dict__["__call__"])

    def __call__(self, shape, dtype):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, shape, dtype):
        return jnp.full(shape, self.value, dtype)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype):
        return jax.random.normal(prandom.next_key(), shape, dtype) * self.std + self.mean


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0, a=-2.0, b=2.0):
        self.mean, self.std, self.a, self.b = mean, std, a, b

    def __call__(self, shape, dtype):
        z = jax.random.truncated_normal(prandom.next_key(), self.a, self.b, shape, jnp.float32)
        return (z * self.std + self.mean).astype(dtype)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype):
        return jax.random.uniform(prandom.next_key(), shape, dtype, self.low, self.high)


class Bilinear(Initializer):
    """reference: initializer/Bilinear — transposed-conv upsampling kernels:
    each [kh, kw] filter is the bilinear interpolation stencil, identical
    across channels. Weight shape [C_out, C_in, kh, kw]."""

    def __call__(self, shape, dtype):
        if len(shape) != 4:
            raise ValueError(f"Bilinear expects a 4-D conv weight, got {shape}")
        kh, kw = shape[2], shape[3]

        def stencil(k):
            f = (k + 1) // 2
            c = f - 1 if k % 2 == 1 else f - 0.5
            return 1.0 - jnp.abs(jnp.arange(k, dtype=jnp.float32) - c) / f

        w = jnp.outer(stencil(kh), stencil(kw))
        return jnp.broadcast_to(w, tuple(shape)).astype(dtype)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return jax.random.normal(prandom.next_key(), shape, jnp.float32).astype(dtype) * std


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(prandom.next_key(), shape, jnp.float32, -limit, limit).astype(dtype)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.negative_slope, self.nonlinearity = fan_in, negative_slope, nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope**2)) if self.nonlinearity == "leaky_relu" else math.sqrt(2.0)
        std = gain / math.sqrt(fi)
        return jax.random.normal(prandom.next_key(), shape, jnp.float32).astype(dtype) * std


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.negative_slope, self.nonlinearity = fan_in, negative_slope, nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope**2)) if self.nonlinearity == "leaky_relu" else math.sqrt(2.0)
        limit = gain * math.sqrt(3.0 / fi)
        return jax.random.uniform(prandom.next_key(), shape, jnp.float32, -limit, limit).astype(dtype)


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype):
        arr = self.value._data if isinstance(self.value, Tensor) else jnp.asarray(np.asarray(self.value))
        return arr.reshape(shape).astype(dtype)


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype):
        return jax.nn.initializers.orthogonal(self.gain)(prandom.next_key(), shape, dtype)


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def __call__(self, shape, dtype):
        out = np.zeros(shape, np.float32)
        oc, ic = shape[0], shape[1]
        mid = tuple(s // 2 for s in shape[2:])
        for i in range(min(oc, ic * self.groups)):
            out[(i, i % ic) + mid] = 1.0
        return jnp.asarray(out).astype(dtype)


def calculate_gain(nonlinearity, param=None):
    gains = {
        "sigmoid": 1.0,
        "linear": 1.0,
        "conv2d": 1.0,
        "tanh": 5.0 / 3.0,
        "relu": math.sqrt(2.0),
        "selu": 3.0 / 4.0,
    }
    if nonlinearity == "leaky_relu":
        return math.sqrt(2.0 / (1 + (param if param is not None else 0.01) ** 2))
    return gains.get(nonlinearity, 1.0)


def set_global_initializer(weight_init, bias_init=None):
    global _global_weight_init, _global_bias_init
    _global_weight_init, _global_bias_init = weight_init, bias_init


_global_weight_init = None
_global_bias_init = None
