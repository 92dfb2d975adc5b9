"""Gradient clipping. Parity: reference python/paddle/nn/clip.py
(ClipGradByGlobalNorm/Norm/Value, applied by optimizers pre-step)."""
from __future__ import annotations

import jax.numpy as jnp

from ..core.tensor import Tensor
from ..ops.dispatch import apply_op

__all__ = ["ClipGradByGlobalNorm", "ClipGradByNorm", "ClipGradByValue",
           "clip_grad_norm_", "clip_grad_value_"]


class ClipGradByGlobalNorm:
    def __init__(self, clip_norm=1.0, group_name="default_group",
                 auto_skip_clip=False):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        """Global-norm clip over fp32 UPCASTS of the raw gradients —
        fully on-device and traceable (a leftover host-fetch `float()`
        reduction here used to break the whole train step out of
        to_static AND pay a per-step host round trip). The scale is a
        function of the gradients only: `moment_dtype`/`fused` narrow
        optimizer STORAGE after clipping, so the clip sees identical
        fp32 values whatever the accumulators store
        (tests/test_fused_optimizer.py pins this)."""
        grads = [g for _, g in params_grads if g is not None]
        if not grads:
            return params_grads
        total = jnp.sqrt(jnp.asarray(
            sum(jnp.sum(jnp.square(g._data.astype(jnp.float32))) for g in grads)))
        scale = jnp.minimum(self.clip_norm / jnp.maximum(total, 1e-6), 1.0)
        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
            else:
                out.append((p, Tensor((g._data.astype(jnp.float32) * scale).astype(g.dtype))))
        return out


class ClipGradByNorm:
    def __init__(self, clip_norm=1.0):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
                continue
            n = jnp.linalg.norm(g._data.astype(jnp.float32))
            scale = jnp.minimum(self.clip_norm / jnp.maximum(n, 1e-6), 1.0)
            out.append((p, Tensor((g._data * scale.astype(g.dtype)))))
        return out


class ClipGradByValue:
    def __init__(self, max, min=None):
        self.max = float(max)
        self.min = float(min) if min is not None else -float(max)

    def __call__(self, params_grads):
        return [(p, Tensor(jnp.clip(g._data, self.min, self.max)) if g is not None else g)
                for p, g in params_grads]


def clip_grad_norm_(parameters, max_norm, norm_type=2.0,
                    error_if_nonfinite=False):
    if isinstance(parameters, Tensor):
        parameters = [parameters]
    grads = [p._grad_buffer for p in parameters if p._grad_buffer is not None]
    if not grads:
        return Tensor(jnp.zeros(()))
    if norm_type == float("inf"):
        total = jnp.max(jnp.stack([jnp.max(jnp.abs(g)) for g in grads]))
    else:
        total = jnp.sum(jnp.stack(
            [jnp.sum(jnp.abs(g.astype(jnp.float32)) ** norm_type) for g in grads])) ** (1.0 / norm_type)
    scale = jnp.minimum(max_norm / jnp.maximum(total, 1e-6), 1.0)
    for p in parameters:
        if p._grad_buffer is not None:
            p._grad_buffer = (p._grad_buffer * scale).astype(p.dtype)
    return Tensor(total)


def clip_grad_value_(parameters, clip_value):
    if isinstance(parameters, Tensor):
        parameters = [parameters]
    for p in parameters:
        if p._grad_buffer is not None:
            p._grad_buffer = jnp.clip(p._grad_buffer, -clip_value, clip_value)
