"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas/pjit.

Architecture (SURVEY.md §7): XLA replaces the reference's kernel library,
executor, and compiler (Phi/InterpreterCore/CINN); this package supplies the
imperative user API (Tensor/Layer/Optimizer/AMP/DataLoader), the parallelism
orchestration (mesh, fleet, TP/PP/ZeRO/SP/EP, auto-parallel), Pallas kernels
for the hot paths, and the launcher/checkpoint/profiler shell.
"""
import time as _time

_T_IMPORT0 = _time.monotonic_ns()  # the set-up log's `setup.import` starts here

from . import framework
from .framework import dtype as _dtype_mod
from .framework.core import Parameter, Tensor, no_grad, to_tensor
from .framework.dtype import (
    bfloat16,
    bool,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .framework.param_attr import ParamAttr
from .framework.random import get_rng_state, seed, set_rng_state

from . import tensor
from .tensor import *  # noqa: F401,F403
from .tensor import creation as _creation

from . import autograd
from .autograd import grad

from . import nn
from . import optimizer
from . import amp
from . import io
from . import metric
from . import device
from . import jit as jit_mod
from .jit_api import jit, to_static

# `paddle.jit` is both the compile decorator and the jit namespace
jit.to_static = jit_mod.to_static
jit.save = jit_mod.save
jit.load = jit_mod.load
jit.not_to_static = jit_mod.not_to_static
jit.enable_to_static = jit_mod.enable_to_static
jit.ignore_module = jit_mod.ignore_module
jit.TrainStep = jit_mod.TrainStep
from . import vision
from . import hapi
from .hapi import Model
from . import distributed
from . import incubate
from . import distribution
from . import quantization
from . import audio
from . import text
from . import observability
from . import profiler
from . import sparse
from . import linalg as _linalg_ns
from . import fft
from . import signal
from . import inference
from . import serving
from . import static
from .serialization import load, save

linalg = tensor.linalg

CPUPlace = device.CPUPlace
TPUPlace = device.TPUPlace
CUDAPlace = device.TPUPlace  # CUDA-script compat: maps to the TPU device
CUDAPinnedPlace = device.CPUPlace

set_device = device.set_device
get_device = device.get_device
is_compiled_with_cuda = lambda: False
is_compiled_with_xpu = lambda: False
is_compiled_with_rocm = lambda: False
is_compiled_with_cinn = lambda: False
is_compiled_with_custom_device = lambda name="tpu": name == "tpu"
is_compiled_with_tpu = lambda: True
in_dynamic_mode = lambda: not static.in_static_mode()
in_dynamic_or_pir_mode = in_dynamic_mode

disable_static = static.disable_static
enable_static = static.enable_static

DataParallel = None  # installed by paddle_tpu.distributed at import time


def _install_dataparallel():
    global DataParallel
    from .distributed.parallel import DataParallel as _DP

    DataParallel = _DP


_install_dataparallel()

disable_signal_handler = lambda: None

from .framework.flags import get_flags, set_flags  # noqa: E402

from . import regularizer
from . import utils
from . import version
from . import hub
from .hapi import callbacks

__version__ = version.full_version
base = framework  # paddle.base compat alias (reference: python/paddle/base)


def iinfo(dtype):
    import numpy as np

    return np.iinfo(np.dtype(_dtype_mod.convert_dtype(dtype)))


def finfo(dtype):
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    d = _dtype_mod.convert_dtype(dtype)
    return ml_dtypes.finfo(d) if d == jnp.bfloat16 else np.finfo(np.dtype(d))


def batch(reader, batch_size, drop_last=False):
    """legacy paddle.batch reader decorator."""

    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


class LazyGuard:
    """reference: paddle.LazyGuard — delayed param init. Params here are
    cheap host arrays until first use, so this is a no-op guard."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class onnx:  # namespace stub (reference: paddle.onnx.export via paddle2onnx)
    @staticmethod
    def export(*a, **k):
        raise NotImplementedError(
            "ONNX export is not part of the TPU-native build; export via "
            "paddle_tpu.jit.save (weights) or AOT-compile with jax.export"
        )



def set_grad_enabled(flag):
    """Applies immediately (paddle semantics); also usable as a context
    manager that restores the previous mode on exit."""
    from .framework import core as _core

    prev = _core._grad_enabled()
    _core._tls.grad_enabled = flag

    class _Guard:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            _core._tls.grad_enabled = prev

    return _Guard()


def is_grad_enabled():
    from .framework import core as _core

    return _core._grad_enabled()


def enable_grad(func=None):
    """reference: paddle.enable_grad — context manager (or decorator)
    forcing gradient tracking on, e.g. inside a no_grad region."""
    guard = set_grad_enabled(True)
    if func is None:
        return guard
    import functools

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        with set_grad_enabled(True):
            return func(*args, **kwargs)

    guard.__exit__()
    return wrapper


def summary(net, input_size=None, dtypes=None, input=None):
    return hapi.summary(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    return 0


__version__ = "0.1.0"

from .observability import tracing as _tracing

_tracing.setup_record("setup.import", _T_IMPORT0, _time.monotonic_ns())
