"""Parameter initializers.

Parity: reference `python/paddle/nn/initializer/` (Constant, Normal,
TruncatedNormal, Uniform, XavierNormal/Uniform, KaimingNormal/Uniform,
Assign, Orthogonal, Dirac).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..framework.random import rng_key
from ..profiler import compile_log as _compile_log

__all__ = [
    "Initializer", "Constant", "Normal", "TruncatedNormal", "Uniform",
    "XavierNormal", "XavierUniform", "KaimingNormal", "KaimingUniform",
    "Assign", "Orthogonal", "calculate_gain",
]


def calculate_gain(nonlinearity, param=None):
    gains = {
        "sigmoid": 1.0, "linear": 1.0, "conv1d": 1.0, "conv2d": 1.0,
        "conv3d": 1.0, "tanh": 5.0 / 3.0, "relu": math.sqrt(2.0),
        "leaky_relu": math.sqrt(2.0 / (1 + (param if param is not None else 0.01) ** 2)),
        "selu": 3.0 / 4.0,
    }
    return gains[nonlinearity]


def _fan_in_out(shape):
    shape = tuple(shape)
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = int(np.prod(shape[2:]))
    # conv weight (out_c, in_c, *k) — paddle computes fan on this layout
    fan_in = shape[1] * receptive
    fan_out = shape[0] * receptive
    return fan_in, fan_out


class Initializer:
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
        return self.mean + self.std * jax.random.normal(rng_key(), shape, dtype)


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0, a=-2.0, b=2.0):
        self.mean, self.std, self.a, self.b = mean, std, a, b

    def __call__(self, shape, dtype):
        z = jax.random.truncated_normal(rng_key(), self.a, self.b, shape, dtype)
        return self.mean + self.std * z


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype):
        return jax.random.uniform(rng_key(), shape, dtype, self.low, self.high)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return std * jax.random.normal(rng_key(), shape, dtype)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(rng_key(), shape, dtype, -limit, limit)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.slope, self.nonlinearity = fan_in, negative_slope, nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.slope)
        std = gain / math.sqrt(fi)
        return std * jax.random.normal(rng_key(), shape, dtype)


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.slope, self.nonlinearity = fan_in, negative_slope, nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.slope)
        limit = gain * math.sqrt(3.0 / fi)
        return jax.random.uniform(rng_key(), shape, dtype, -limit, limit)


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype):
        v = self.value._data if isinstance(self.value, Tensor) else jnp.asarray(self.value)
        return v.astype(dtype).reshape(shape)


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype):
        rows = shape[0]
        cols = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        flat = jax.random.normal(rng_key(), (max(rows, cols), min(rows, cols)), jnp.float32)
        q, r = jnp.linalg.qr(flat)
        q = q * jnp.sign(jnp.diag(r))
        if rows < cols:
            q = q.T
        return (self.gain * q[:rows, :cols]).reshape(shape).astype(dtype)


# default initializer used by create_parameter
def _init_tensor(shape, dtype, initializer=None, is_bias=False):
    # a setup.param_init span: the draw's host time (dispatched, never
    # synced here) and the compiles of the initializer's ops
    with _compile_log.setup_span("setup.param_init") as span:
        if initializer is None:
            initializer = _global_initializer["bias" if is_bias else "weight"]
        if initializer is None:
            initializer = Constant(0.0) if is_bias else XavierUniform()
        if callable(initializer) and not isinstance(initializer, Initializer):
            # support paddle-style ParamAttr(initializer=...) or plain callables
            init = initializer
            arr = init(shape, dtype)
            arr = arr._data if isinstance(arr, Tensor) else arr
        else:
            arr = initializer(shape, dtype)
        t = Tensor(arr, stop_gradient=False)
        t._is_param = True
        span.counts["leaves"] = 1
        span.counts["bytes"] = math.prod(t._data.shape) \
            * np.dtype(t._data.dtype).itemsize
    return t


class Dirac(Initializer):
    """Parity: nn.initializer.Dirac — identity-preserving conv init:
    weight[i, i % in_c, center...] = 1 (groups split the identity)."""

    def __init__(self, groups=1, name=None):
        self.groups = groups

    def __call__(self, shape, dtype):
        import numpy as np
        if len(shape) < 3:
            raise ValueError("Dirac needs a conv weight (>=3 dims)")
        w = np.zeros(shape, np.float32)
        out_c, in_c = shape[0], shape[1]
        per = out_c // self.groups
        center = tuple(s // 2 for s in shape[2:])
        for i in range(out_c):
            w[(i,) + ((i % per) % in_c,) + center] = 1.0
        return jnp.asarray(w, dtype)


class Bilinear(Initializer):
    """Parity: nn.initializer.Bilinear — upsampling-kernel init for
    transposed conv weights."""

    def __call__(self, shape, dtype):
        import numpy as np
        if len(shape) < 4:
            raise ValueError("Bilinear needs a 4-D conv weight")
        kh, kw = shape[-2], shape[-1]
        fh, fw = (kh + 1) // 2, (kw + 1) // 2
        cy = fh - 1 if kh % 2 == 1 else fh - 0.5
        cx = fw - 1 if kw % 2 == 1 else fw - 0.5
        yy, xx = np.meshgrid(np.arange(kh), np.arange(kw), indexing="ij")
        filt = (1 - np.abs(yy - cy) / fh) * (1 - np.abs(xx - cx) / fw)
        w = np.zeros(shape, np.float32)
        w[range(shape[0]), list(np.arange(shape[0]) % shape[1]), :, :] = filt
        return jnp.asarray(w, dtype)


_global_initializer = {"weight": None, "bias": None}


def set_global_initializer(weight_init, bias_init=None):
    """Parity: nn.initializer.set_global_initializer — default inits for
    subsequently created parameters (None restores the built-ins)."""
    _global_initializer["weight"] = weight_init
    _global_initializer["bias"] = bias_init


__all__ += ["Dirac", "Bilinear", "set_global_initializer"]


# module-path parity: nn.initializer.lazy_init
from . import initializer_lazy as lazy_init  # noqa: E402
from .initializer_lazy import LazyGuard  # noqa: E402,F401
__all__ += ["lazy_init", "LazyGuard"]
