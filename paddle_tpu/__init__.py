"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas/pjit.

Top-level namespace parity: reference `python/paddle/__init__.py` — Tensor,
creation/math/manipulation ops, nn, optimizer, amp, autograd, io,
distributed, jit, vision, profiler.
"""
from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()     # setup.import starts here (compile_log)

import os  # noqa: E402

# 64-bit dtypes on (paddle's default int dtype is int64). Floats still default
# to float32 via get_default_dtype; float64 only on explicit request.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"

from .core.dtype import (  # noqa: F401,E402
    bool_, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype, finfo, iinfo, dtype_name,
)
from .core.tensor import SelectedRows, Tensor, to_tensor, is_tensor  # noqa: F401,E402
from .core import autograd as _autograd_core  # noqa: E402
from .core.autograd import no_grad, enable_grad, set_grad_enabled, is_grad_enabled  # noqa: F401,E402
from .core.autograd import grad  # noqa: F401,E402

from .ops import *  # noqa: F401,F403,E402
from .ops import methods as _methods  # noqa: E402
from .ops import dispatch  # noqa: F401,E402

_methods.patch_tensor_methods()

from .framework.random import seed, get_rng_state, set_rng_state  # noqa: F401,E402
from .framework import save, load  # noqa: F401,E402

from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import autograd  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from .hapi.summary import flops, summary  # noqa: F401,E402
from .utils.flags import get_flags, set_flags  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import geometric  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import fft  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import tensor  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from . import strings  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import version  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from .compat import (  # noqa: F401,E402
    CPUPlace, CUDAPlace, CUDAPinnedPlace, XPUPlace, CustomPlace, shape,
    tolist, reverse, batch, set_printoptions, disable_signal_handler,
    check_shape, set_cuda_rng_state, get_cuda_rng_state)
from .compat import _export_inplace as _exp_inp  # noqa: E402
_exp_inp(globals())
del _exp_inp

# remaining reference top-level aliases
from .nn.utils_ import ParamAttr  # noqa: F401,E402
bool = bool_  # noqa: F401,E402  (paddle.bool dtype alias, like reference)
import numpy as _np  # noqa: E402
dtype = _np.dtype  # Tensor.dtype values are numpy dtype instances, so
# isinstance(x.dtype, paddle.dtype) holds — the reference idiom
floor_mod = mod  # noqa: F811,E402
floor_mod_ = globals().get("mod_", None) or floor_mod


class LazyGuard:
    """Parity: paddle.LazyGuard — defers parameter initialization in the
    reference; initialization here is already lazy-cheap (jax arrays
    materialize on first use), so the guard is a no-op context."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
from .ops import linalg  # noqa: F401,E402
from .hapi import callbacks  # noqa: F401,E402

from .nn.layer.layers import Layer  # noqa: F401,E402
from .hapi.model import Model  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402


def disable_static(place=None):
    """No-op: paddle_tpu is always in eager (dygraph) mode; compiled execution
    is opt-in via paddle_tpu.jit.to_static. Kept for API parity."""


def enable_static():
    raise RuntimeError(
        "paddle_tpu has no separate static-graph mode: use "
        "paddle_tpu.jit.to_static(fn) to get compiled (XLA) execution.")


def in_dynamic_mode():
    return True


# setup.import ends here: recorded, not entered, since the profiler that
# would annotate it could not be imported before the package
profiler.compile_log.record_span("setup.import", _T_IMPORT,
                                 _time.perf_counter())
del _T_IMPORT, _time
