"""Optimizer base + the standard zoo.

Parity: reference `python/paddle/optimizer/optimizer.py:127` (Optimizer base:
regularization, grad clip, LR scheduling, accumulators) and the phi optimizer
kernels (sgd/momentum/adam/adamw/lamb...). Updates are jnp expressions, so a
whole `opt.step()` traces into the fused train step under to_static — the
analog of the reference's fused_adam multi-tensor kernels is XLA fusing the
update across parameters.

Master weights: with multi_precision=True (or AMP O2), accumulators and the
update run in fp32 while the parameter stays bf16/fp16
(reference: fleet/utils/mix_precision_utils.py + master_weight in adamw).

bf16 optimizer states (TPU-native extension): `moment_dtype="bfloat16"`
(or FLAGS_bf16_optimizer_states=1 as the global default) STORES every
accumulator in bf16 while the update math still runs in fp32 (upcast on
read, downcast on store; master weights stay fp32). The AdamW update is
HBM-bound at the roofline (an early chip reading of ~21 ms for 608M
fp32 states, not re-measured since), so halving the moment bytes is the one remaining
flagship-MFU lever. Reference analog: the low-precision moments path of
fused_adam / PaddleNLP's bf16 optimizer
(paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu uses MT=fp32 compute
over narrow stored moments the same way).

Fused update (ISSUE 9): `AdamW(..., fused=True)` (or
FLAGS_fused_optimizer=1 as the global default) packs every eligible
parameter leaf into padded flat buckets (kernels/fused_optimizer.py —
one (rows, 128) bucket per (param dtype, effective-lr, decay-on)
group) and performs the whole AdamW update in ONE Pallas pass: one
read and one write per state byte instead of XLA's per-leaf
upcast/downcast round trips. Moments and fp32 master weights then LIVE
in bucket form (accumulator slots "fused_m"/"fused_v"/"fused_master"
keyed by bucket id — raw_state round-trips them through the to_static
donated-buffer step unchanged), while `state_dict()` de-bucketizes to
the canonical per-parameter `moment1_i`/`moment2_i`/`master_i` keys so
checkpoints stay interchangeable with the unfused optimizer (and
`set_state_dict` re-buckets lazily at the next step). Eligibility:
fp32 parameters, or narrow parameters under multi_precision=True (a
narrow parameter WITHOUT a master weight keeps the eager per-leaf
path — fused compute is fp32 by contract and would silently change
its numerics); amsgrad keeps the eager path too. Non-fused optimizers
(SGD/Lamb/LBFGS/...) ignore the flag entirely.

ZeRO-1 (same bucket layout): when the active fleet mesh has
sharding_degree > 1, the fused path shards the moment and master
buckets over the 'sharding' axis (GSPMD constraints, no shard_map) —
each rank updates rows/degree of optimizer state and the replication
constraint on the param bucket is the parameter all-gather. Per-chip
optimizer-state bytes drop by the sharding degree; see BASELINE.md for
the sizing math.

Grad clip x narrow states: grad clip runs BEFORE any accumulator is
touched, on fp32 upcasts of the raw gradients (nn/clip.py), so the
clip scale is identical whatever `moment_dtype` or `fused` say —
moments narrow only at storage, and with multi_precision=False the
fp32 parameter IS the master value the clipped update applies to.
tests/test_fused_optimizer.py pins both properties.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import autograd
from .. import profiler as _profiler
from ..profiler import monitor as _monitor
from ..profiler.monitor import grad_global_norm
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adagrad",
           "Adadelta", "Adamax", "RMSProp", "Lamb", "NAdam", "RAdam", "ASGD",
           "Rprop", "LBFGS"]


def _register_moment_flag():
    from ..utils.flags import define_flag
    define_flag("bf16_optimizer_states", False,
                "store optimizer accumulators in bfloat16 (fp32 compute)")
    define_flag("fused_optimizer", False,
                "use the fused multi-tensor Pallas update for optimizers "
                "that support it (AdamW)")


_register_moment_flag()

# accumulator slots that hold BUCKETED fused state (kernels/
# fused_optimizer.py layouts) rather than per-parameter arrays;
# state_dict() de-bucketizes them, raw_state() passes them through
_FUSED_SLOTS = ("fused_m", "fused_v", "fused_master")


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False,
                 moment_dtype=None, fused=None):
        if parameters is None:
            raise ValueError(
                "paddle_tpu optimizers require an explicit parameter list "
                "(pass model.parameters()).")
        self._parameter_list = list(parameters)
        self._param_groups = None
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            self._param_groups = self._parameter_list
            flat = []
            for g in self._param_groups:
                flat.extend(g["params"])
            self._parameter_list = flat
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        if isinstance(weight_decay, (int, float)):
            self._weight_decay = float(weight_decay)
        else:
            self._weight_decay = weight_decay  # None or regularizer-like
        # accumulators: slot name -> param index -> array
        self._accumulators: Dict[str, Dict[int, jax.Array]] = {}
        self._master_weights: Dict[int, jax.Array] = {}
        self._step_count = 0
        if moment_dtype is None:
            from ..utils.flags import flags
            if flags("bf16_optimizer_states"):
                moment_dtype = "bfloat16"
        self._moment_dtype = jnp.dtype(moment_dtype) \
            if moment_dtype is not None else None
        if fused is None:
            from ..utils.flags import flags
            fused = bool(flags("fused_optimizer"))
        # only optimizers that implement _fused_step (AdamW) ever act on
        # this; for the rest the flag is inert by construction
        self._fused = bool(fused)
        # bucket bookkeeping (fused path): group key -> {uid, layout,
        # sig, ...}; geometry is rebuilt deterministically from the
        # parameter list, only the ARRAYS live in _accumulators
        self._fused_buckets: Dict = {}

    # ------------------------------------------------------------------- lr
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # ----------------------------------------------------------- accumulators
    def _acc(self, name: str, idx: int, like: jax.Array, fill=0.0) -> jax.Array:
        """Accumulator READ: with moment_dtype set, storage is narrow but
        the returned view is upcast to fp32 so every optimizer's update
        math runs full-precision unchanged (XLA fuses the converts into
        the update, so the HBM traffic is the narrow array)."""
        slot = self._accumulators.setdefault(name, {})
        if idx not in slot:
            dtype = self._moment_dtype if self._moment_dtype is not None \
                else (jnp.float32 if self._multi_precision else like.dtype)
            slot[idx] = jnp.full(like.shape, fill, dtype)
        a = slot[idx]
        if self._moment_dtype is not None and a.dtype == self._moment_dtype:
            return a.astype(jnp.float32)
        return a

    def _set_acc(self, name: str, idx: int, value):
        if self._moment_dtype is not None:
            value = value.astype(self._moment_dtype)
        self._accumulators[name][idx] = value

    def _master(self, idx: int, p: Tensor) -> jax.Array:
        if not self._multi_precision or p.dtype == jnp.float32:
            return p._data
        if idx not in self._master_weights:
            self._master_weights[idx] = p._data.astype(jnp.float32)
        return self._master_weights[idx]

    def _writeback(self, idx: int, p: Tensor, new_master):
        if self._multi_precision and p.dtype != jnp.float32:
            self._master_weights[idx] = new_master
            p._data = new_master.astype(p.dtype)
        else:
            p._data = new_master

    # ------------------------------------------------------------------ step
    @autograd.no_grad
    def step(self):
        # profiler span (ISSUE 11): optimizer time shows on the host
        # timeline next to dispatch op spans — one attribute check when
        # no Profiler records (the ops.dispatch pattern)
        if _profiler._tracer.enabled:
            with _profiler.RecordEvent(
                    "optimizer.step", _profiler.TracerEventType.Optimization):
                return self._step_impl()
        return self._step_impl()

    minimize_step = step

    def _step_impl(self):
        params_grads = []
        for p in self._parameter_list:
            if p.stop_gradient or p._grad_buffer is None:
                continue
            params_grads.append((p, Tensor(p._grad_buffer)))
        # TrainingMonitor hook (ISSUE 11): the PRE-clip gradient global
        # norm + lr, stashed lazily for the monitor's next step() fetch.
        # With no monitor attached this is ONE module-global truthiness
        # check — asserted allocation-free by the booby-trap test. Under
        # a to_static trace grads are tracers and grad_global_norm
        # returns None (the python-side hook must not leak tracers).
        if _monitor._ACTIVE:
            mon = _monitor._ACTIVE[-1]
            gn = grad_global_norm(self._parameter_list) \
                if mon.track_grad_norm else None
            mon.note(lr=self.get_lr(), grad_norm=gn)
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        lr = self.get_lr()
        self._step_count += 1
        if self._fused and params_grads:
            # returns the (p, g) pairs the fused path did NOT handle;
            # base implementation handles nothing (flag inert for
            # optimizers without a fused update)
            if _profiler._tracer.enabled:
                with _profiler.RecordEvent(
                        "optimizer.fused_step",
                        _profiler.TracerEventType.Optimization):
                    params_grads = self._fused_step(params_grads, lr)
            else:
                params_grads = self._fused_step(params_grads, lr)
        for idx, p in enumerate(self._parameter_list):
            match = next((g for (pp, g) in params_grads if pp is p), None)
            if match is None:
                continue
            g = match._data
            lr_scale = getattr(p, "_lr_scale", 1.0)
            self._apply_one(idx, p, g, lr * lr_scale)

    def _fused_step(self, params_grads, lr):
        """Fused multi-tensor hook: handle what you can, return the
        rest for the per-parameter loop. Base: nothing is handled."""
        return params_grads

    # ----------------------------------------------- fused bucket plumbing
    @staticmethod
    def _fused_mesh():
        """(mesh, degree) of the active 'sharding' axis, or (None, 1) —
        degree > 1 turns the fused update into ZeRO-1."""
        try:
            from ..distributed.fleet import fleet as fleet_mod
            mesh = getattr(getattr(fleet_mod, "_hcg", None), "mesh", None)
        except Exception:
            mesh = None
        if mesh is None:
            return None, 1
        degree = dict(mesh.shape).get("sharding", 1)
        return (mesh, degree) if degree > 1 else (None, 1)

    def _fused_state_entries(self):
        """Per-parameter view of every bucketed slot (for state_dict):
        {canonical_key: array} by slicing the live buckets."""
        from ..kernels.fused_optimizer import unpack_bucket
        out = {}
        for rec in self._fused_buckets.values():
            uid, layout = rec["uid"], rec["layout"]
            for slot, canon in (("fused_m", "moment1"),
                                ("fused_v", "moment2"),
                                ("fused_master", "master")):
                bucket = self._accumulators.get(slot, {}).get(uid)
                if bucket is None:
                    continue
                for arr, (idx, _, _, _) in zip(
                        unpack_bucket(bucket, layout), layout.entries):
                    out[f"{canon}_{idx}"] = arr
        return out

    def _drop_fused_buckets(self, debucketize=False):
        """Forget bucketed storage — optionally writing it back to the
        canonical per-parameter slots first (layout-change path)."""
        if debucketize:
            for key, arr in self._fused_state_entries().items():
                name, idx = key.rsplit("_", 1)
                if name == "master":
                    self._master_weights[int(idx)] = arr
                else:
                    self._accumulators.setdefault(name, {})[int(idx)] = arr
        for slot in _FUSED_SLOTS:
            self._accumulators.pop(slot, None)
        self._fused_buckets.clear()

    def _apply_one(self, idx: int, p: Tensor, g: jax.Array, lr: float):
        raise NotImplementedError

    def _decayed_grad(self, p, g):
        """L2-regularizer-style decay (coupled; AdamW overrides w/ decoupled).
        Accepts paddle.regularizer objects (L1Decay adds coeff*sign(w))."""
        wd = self._weight_decay
        if isinstance(wd, float) and wd != 0.0:
            return g + wd * p._data.astype(g.dtype)
        if wd is not None and hasattr(wd, "apply"):
            return wd.apply(p._data, g)
        return g

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        """backward + apply. Matches the reference contract
        (python/paddle/optimizer/optimizer.py Optimizer.minimize): does
        NOT clear gradients — p.grad stays inspectable afterwards, the
        caller owns clear_grad() — and returns (optimize_ops,
        params_grads); optimize_ops is [] in dygraph."""
        loss.backward()
        params_grads = [(p, p.grad) for p in self._parameter_list
                        if p.grad is not None]
        self.step()
        return [], params_grads

    # --------------------------------------------------------------- state IO
    def state_dict(self):
        out = {}
        for name, slot in self._accumulators.items():
            if name in _FUSED_SLOTS:
                continue    # exported in canonical per-parameter form below
            for idx, arr in slot.items():
                out[f"{name}_{idx}"] = Tensor(arr)
        for idx, arr in self._master_weights.items():
            out[f"master_{idx}"] = Tensor(arr)
        # bucketed fused state de-bucketizes to the same canonical keys
        # the unfused optimizer writes, so checkpoints are
        # interchangeable across fused=True/False
        for key, arr in self._fused_state_entries().items():
            out[key] = Tensor(arr)
        out["@step"] = self._step_count
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        # canonical per-parameter entries rule: stale buckets would
        # shadow them at the next fused step, so DEBUCKETIZE into the
        # canonical slots first (a PARTIAL state dict must overwrite
        # only the keys it carries, same as the unfused path — dropping
        # the buckets outright would silently zero the rest), then let
        # the incoming entries overwrite; the fused path re-buckets
        # from the canonical slots lazily at the next step
        self._drop_fused_buckets(debucketize=True)
        for key, v in state.items():
            if key == "@step":
                self._step_count = int(v)
            elif key == "LR_Scheduler":
                if isinstance(self._learning_rate, LRScheduler):
                    self._learning_rate.set_state_dict(v)
            elif key.startswith("master_"):
                self._master_weights[int(key[7:])] = \
                    v._data if isinstance(v, Tensor) else jnp.asarray(v)
            else:
                name, idx = key.rsplit("_", 1)
                arr = v._data if isinstance(v, Tensor) else jnp.asarray(v)
                self._accumulators.setdefault(name, {})[int(idx)] = arr
        return self

    # ------------------------------------------- functional-state (jit bridge)
    def raw_state(self):
        st = {f"{n}_{i}": a for n, slot in self._accumulators.items()
              for i, a in slot.items()}
        st.update({f"master_{i}": a for i, a in self._master_weights.items()})
        return st

    def load_raw_state(self, raw):
        for key, arr in raw.items():
            if key.startswith("master_"):
                self._master_weights[int(key[7:])] = arr
            else:
                name, idx = key.rsplit("_", 1)
                self._accumulators.setdefault(name, {})[int(idx)] = arr


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m = self._master(idx, p)
        self._writeback(idx, p, m - lr * g.astype(m.dtype))


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m = self._master(idx, p)
        g = g.astype(m.dtype)
        vel = self._acc("velocity", idx, m)
        vel = self._momentum * vel + g
        self._set_acc("velocity", idx, vel)
        if self._nesterov:
            update = g + self._momentum * vel
        else:
            update = vel
        self._writeback(idx, p, m - lr * update)


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, name=None, amsgrad=False,
                 moment_dtype=None, fused=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, moment_dtype=moment_dtype,
                         fused=fused)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._amsgrad = amsgrad

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        m = self._acc("moment1", idx, m_w)
        v = self._acc("moment2", idx, m_w)
        t = self._step_count
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_acc("moment1", idx, m)
        self._set_acc("moment2", idx, v)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        if self._amsgrad:
            vmax = self._acc("moment2_max", idx, m_w)
            vmax = jnp.maximum(vmax, vhat)
            self._set_acc("moment2_max", idx, vmax)
            vhat = vmax
        self._writeback(idx, p, m_w - lr * mhat / (jnp.sqrt(vhat) + self._eps))


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None,
                 amsgrad=False, moment_dtype=None, fused=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision,
                         name=name, amsgrad=amsgrad,
                         moment_dtype=moment_dtype, fused=fused)
        from ..regularizer import L1Decay, L2Decay
        if isinstance(weight_decay, L1Decay):
            # parity: reference AdamW rejects regularizer objects — a
            # silent float() would turn L1 into decoupled L2 decay
            raise TypeError(
                "AdamW applies decoupled L2 decay; L1Decay is not "
                "supported (use Adam with weight_decay=L1Decay(...))")
        if isinstance(weight_decay, L2Decay):
            weight_decay = weight_decay.coeff
        self._wd = float(weight_decay) if weight_decay else 0.0
        self._apply_decay_fn = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _apply_one(self, idx, p, g, lr):
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        m_w = self._master(idx, p)
        if self._wd != 0.0 and (self._apply_decay_fn is None or
                                self._apply_decay_fn(p.name or f"param_{idx}")):
            m_w = m_w * (1.0 - lr * self._wd)
        g = g.astype(m_w.dtype)
        m = self._acc("moment1", idx, m_w)
        v = self._acc("moment2", idx, m_w)
        t = self._step_count
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_acc("moment1", idx, m)
        self._set_acc("moment2", idx, v)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        if self._amsgrad:
            vmax = self._acc("moment2_max", idx, m_w)
            vmax = jnp.maximum(vmax, vhat)
            self._set_acc("moment2_max", idx, vmax)
            vhat = vmax
        self._writeback(idx, p, m_w - lr * mhat / (jnp.sqrt(vhat) + self._eps))

    # ------------------------------------------------------- fused update
    def _fused_eligible(self, p) -> bool:
        """Fused compute is fp32 by contract: fp32 parameters, or
        narrow parameters whose fp32 truth is a master weight. A narrow
        parameter WITHOUT a master runs its eager bf16/fp16 update
        unchanged (fusing it would silently improve its numerics)."""
        if p._data.dtype == jnp.float32:
            return True
        return self._multi_precision and \
            p._data.dtype in (jnp.bfloat16, jnp.float16)

    def _fused_step(self, params_grads, lr):
        """Bucketed multi-tensor AdamW (kernels/fused_optimizer.py).

        Groups eligible parameters by (dtype, effective-lr, decay-on),
        packs each group into one padded (rows, 128) bucket, and runs
        the whole update in one Pallas pass (one read + one write per
        state byte). Moments/master weights persist IN bucket form
        under the "fused_m"/"fused_v"/"fused_master" accumulator slots;
        with an active 'sharding' mesh axis the update runs ZeRO-1
        sharded. Returns the pairs the fused path does not cover
        (narrow params without master, amsgrad)."""
        if self._amsgrad:
            return params_grads
        from ..kernels.fused_optimizer import (
            adamw_scalars, build_bucket_layout, fused_adamw_bucket,
            fused_adamw_zero1, pack_bucket, unpack_bucket)

        mesh, degree = self._fused_mesh()
        idx_of = {id(p): i for i, p in enumerate(self._parameter_list)}
        groups: Dict = {}
        leftover = []
        for p, g in params_grads:
            if not self._fused_eligible(p):
                leftover.append((p, g))
                continue
            idx = idx_of[id(p)]
            lr_mult = float(getattr(p, "_lr_scale", 1.0))
            if self._lr_ratio is not None:
                lr_mult *= float(self._lr_ratio(p))
            decay_on = self._wd != 0.0 and (
                self._apply_decay_fn is None
                or self._apply_decay_fn(p.name or f"param_{idx}"))
            key = (str(p._data.dtype), lr_mult, bool(decay_on))
            groups.setdefault(key, []).append((idx, p, g._data))

        if not groups:
            return leftover
        ordered = sorted(groups.items(), key=lambda kv: kv[1][0][0])
        # geometry guard: any layout drift de-bucketizes everything back
        # to the canonical slots and rebuilds — moments survive the
        # migration. Two triggers: (a) an existing group's sig changed
        # (new/lost grads in it, dtype or sharding-degree change, uid
        # shift from group reordering); (b) a whole group VANISHED —
        # its bucket would otherwise linger under a uid a new group can
        # be assigned, silently adopting or clobbering foreign moments
        rebuild = bool(set(self._fused_buckets) - {k for k, _ in ordered})
        for uid, (key, members) in enumerate(ordered):
            sig = (uid, degree,
                   tuple((idx, p._data.shape) for idx, p, _ in members))
            rec = self._fused_buckets.get(key)
            if rec is not None and rec["sig"] != sig:
                rebuild = True
        if rebuild:
            self._drop_fused_buckets(debucketize=True)

        for uid, (key, members) in enumerate(ordered):
            param_dtype, lr_mult, decay_on = key
            lr_eff = lr * lr_mult
            rec = self._fused_buckets.get(key)
            if rec is None:
                layout = build_bucket_layout(
                    [(idx, p._data.shape) for idx, p, _ in members],
                    sharding_degree=degree)
                sig = (uid, degree,
                       tuple((idx, p._data.shape) for idx, p, _ in members))
                rec = {"uid": uid, "layout": layout, "sig": sig}
                self._fused_buckets[key] = rec
            layout = rec["layout"]
            has_master = jnp.dtype(param_dtype) != jnp.float32
            mdtype = self._moment_dtype if self._moment_dtype is not None \
                else jnp.float32
            self._seed_fused_bucket(uid, layout, members, mdtype,
                                    has_master, mesh)
            g_bucket = pack_bucket([g for _, _, g in members], layout,
                                   jnp.dtype(param_dtype))
            if has_master:
                w_bucket = self._accumulators["fused_master"][uid]
            else:
                w_bucket = pack_bucket([p._data for _, p, _ in members],
                                       layout, jnp.float32)
            m_bucket = self._accumulators["fused_m"][uid]
            v_bucket = self._accumulators["fused_v"][uid]
            scalars = adamw_scalars(lr_eff, self._beta1, self._beta2,
                                    self._eps,
                                    self._wd if decay_on else 0.0,
                                    self._step_count)
            if mesh is not None:
                p_new, w_new, m_new, v_new = fused_adamw_zero1(
                    g_bucket, w_bucket, m_bucket, v_bucket, scalars, mesh,
                    param_dtype=jnp.dtype(param_dtype) if has_master
                    else None)
            else:
                p_new, w_new, m_new, v_new = fused_adamw_bucket(
                    g_bucket, w_bucket, m_bucket, v_bucket, scalars,
                    param_dtype=jnp.dtype(param_dtype) if has_master
                    else None)
            self._accumulators["fused_m"][uid] = m_new
            self._accumulators["fused_v"][uid] = v_new
            if has_master:
                self._accumulators["fused_master"][uid] = w_new
            for arr, (_, p, _) in zip(unpack_bucket(p_new, layout), members):
                p._data = arr
        return leftover

    def _seed_fused_bucket(self, uid, layout, members, mdtype,
                           has_master, mesh):
        """Materialize a group's m/v (+ master) buckets if absent —
        from the canonical per-parameter slots when present (checkpoint
        reload / migration from the eager path), else zeros / fp32
        param casts, matching the eager accumulators' init exactly.
        Consumed per-parameter entries are removed so state never
        exists twice. Sharded placement happens at creation; once
        placed, updates inherit the layout (no per-step device_put)."""
        from ..kernels.fused_optimizer import pack_bucket, LANES
        m_slot = self._accumulators.setdefault("fused_m", {})
        v_slot = self._accumulators.setdefault("fused_v", {})
        w_slot = self._accumulators.setdefault("fused_master", {})

        def place(arr):
            if mesh is None:
                return arr
            from jax.sharding import NamedSharding, PartitionSpec as P
            return jax.device_put(
                arr, NamedSharding(mesh, P("sharding", None)))

        shape = (layout.rows, LANES)
        for slot, canon, dtype in ((m_slot, "moment1", mdtype),
                                   (v_slot, "moment2", mdtype)):
            cur = slot.get(uid)
            if cur is not None and cur.shape == shape and cur.dtype == dtype:
                continue
            canon_slot = self._accumulators.get(canon, {})
            parts = []
            for idx, p, _ in members:
                prev = canon_slot.pop(idx, None)
                parts.append(jnp.zeros(p._data.shape, dtype) if prev is None
                             else prev.astype(dtype))
            slot[uid] = place(pack_bucket(parts, layout, dtype))
        if has_master:
            cur = w_slot.get(uid)
            if cur is None or cur.shape != shape:
                parts = []
                for idx, p, _ in members:
                    prev = self._master_weights.pop(idx, None)
                    parts.append(p._data.astype(jnp.float32)
                                 if prev is None else prev)
                w_slot[uid] = place(pack_bucket(parts, layout, jnp.float32))


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-06, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None, initial_accumulator_value=0.0):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        acc = self._acc("moment", idx, m_w, fill=self._init_acc)
        acc = acc + g * g
        self._set_acc("moment", idx, acc)
        self._writeback(idx, p, m_w - lr * g / (jnp.sqrt(acc) + self._eps))


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._eps, self._rho = epsilon, rho

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        avg_sq = self._acc("avg_squared_grad", idx, m_w)
        avg_up = self._acc("avg_squared_update", idx, m_w)
        avg_sq = self._rho * avg_sq + (1 - self._rho) * g * g
        update = -jnp.sqrt((avg_up + self._eps) / (avg_sq + self._eps)) * g
        avg_up = self._rho * avg_up + (1 - self._rho) * update * update
        self._set_acc("avg_squared_grad", idx, avg_sq)
        self._set_acc("avg_squared_update", idx, avg_up)
        self._writeback(idx, p, m_w + lr * update)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        m = self._acc("moment", idx, m_w)
        u = self._acc("inf_norm", idx, m_w)
        m = self._beta1 * m + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * u, jnp.abs(g))
        self._set_acc("moment", idx, m)
        self._set_acc("inf_norm", idx, u)
        t = self._step_count
        self._writeback(idx, p,
                        m_w - lr / (1 - self._beta1 ** t) * m / (u + self._eps))


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-06, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        ms = self._acc("mean_square", idx, m_w)
        mom = self._acc("momentum", idx, m_w)
        ms = self._rho * ms + (1 - self._rho) * g * g
        self._set_acc("mean_square", idx, ms)
        if self._centered:
            mg = self._acc("mean_grad", idx, m_w)
            mg = self._rho * mg + (1 - self._rho) * g
            self._set_acc("mean_grad", idx, mg)
            denom = jnp.sqrt(ms - mg * mg + self._eps)
        else:
            denom = jnp.sqrt(ms + self._eps)
        mom = self._momentum * mom + lr * g / denom
        self._set_acc("momentum", idx, mom)
        self._writeback(idx, p, m_w - mom)


class Lamb(Optimizer):
    """Parity: python/paddle/optimizer/lamb.py (layerwise adaptive scaling)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-06, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _apply_one(self, idx, p, g, lr):
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        m = self._acc("moment1", idx, m_w)
        v = self._acc("moment2", idx, m_w)
        t = self._step_count
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_acc("moment1", idx, m)
        self._set_acc("moment2", idx, v)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._eps)
        wd = self._lamb_wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        r = r + wd * m_w
        w_norm = jnp.linalg.norm(m_w)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        self._writeback(idx, p, m_w - lr * trust * r)


class NAdam(Adam):
    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        m = self._acc("moment1", idx, m_w)
        v = self._acc("moment2", idx, m_w)
        t = self._step_count
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_acc("moment1", idx, m)
        self._set_acc("moment2", idx, v)
        mhat = self._beta1 * m / (1 - self._beta1 ** (t + 1)) + \
            (1 - self._beta1) * g / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        self._writeback(idx, p, m_w - lr * mhat / (jnp.sqrt(vhat) + self._eps))


class RAdam(Adam):
    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        m = self._acc("moment1", idx, m_w)
        v = self._acc("moment2", idx, m_w)
        t = self._step_count
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_acc("moment1", idx, m)
        self._set_acc("moment2", idx, v)
        mhat = m / (1 - self._beta1 ** t)
        rho_inf = 2 / (1 - self._beta2) - 1
        rho_t = rho_inf - 2 * t * self._beta2 ** t / (1 - self._beta2 ** t)
        if rho_t > 4:
            vhat = jnp.sqrt(v / (1 - self._beta2 ** t))
            rt = ((rho_t - 4) * (rho_t - 2) * rho_inf /
                  ((rho_inf - 4) * (rho_inf - 2) * rho_t)) ** 0.5
            self._writeback(idx, p, m_w - lr * rt * mhat / (vhat + self._eps))
        else:
            self._writeback(idx, p, m_w - lr * mhat)


class ASGD(Optimizer):
    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def _apply_one(self, idx, p, g, lr):
        g = self._decayed_grad(p, g)
        m_w = self._master(idx, p)
        self._writeback(idx, p, m_w - lr * g.astype(m_w.dtype))


class Rprop(Optimizer):
    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._lr_range = learning_rate_range
        self._etas = etas

    def _apply_one(self, idx, p, g, lr):
        m_w = self._master(idx, p)
        g = g.astype(m_w.dtype)
        prev_g = self._acc("prev_grad", idx, m_w)
        step = self._acc("step_size", idx, m_w, fill=self.get_lr())
        sign = jnp.sign(g * prev_g)
        step = jnp.where(sign > 0, jnp.minimum(step * self._etas[1], self._lr_range[1]),
                         jnp.where(sign < 0,
                                   jnp.maximum(step * self._etas[0], self._lr_range[0]),
                                   step))
        g_eff = jnp.where(sign < 0, jnp.zeros_like(g), g)
        self._set_acc("prev_grad", idx, g_eff)
        self._set_acc("step_size", idx, step)
        self._writeback(idx, p, m_w - jnp.sign(g_eff) * step)


class LBFGS(Optimizer):
    """Simplified LBFGS (single tensor-group, history-based two-loop)."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-07, tolerance_change=1e-09, history_size=100,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._history_size = history_size
        self._s_hist: List = []
        self._y_hist: List = []
        self._prev_flat = None
        self._prev_grad = None

    def _flatten(self, arrays):
        return jnp.concatenate([a.reshape(-1) for a in arrays])

    def step(self, closure=None):
        if closure is not None:
            with autograd.enable_grad():
                loss = closure()
        params = [p for p in self._parameter_list
                  if not p.stop_gradient and p._grad_buffer is not None]
        if not params:
            return
        flat_g = self._flatten([p._grad_buffer.astype(jnp.float32) for p in params])
        flat_w = self._flatten([p._data.astype(jnp.float32) for p in params])
        if self._prev_flat is not None:
            s = flat_w - self._prev_flat
            y = flat_g - self._prev_grad
            if float(jnp.dot(s, y)) > 1e-10:
                self._s_hist.append(s)
                self._y_hist.append(y)
                if len(self._s_hist) > self._history_size:
                    self._s_hist.pop(0)
                    self._y_hist.pop(0)
        q = flat_g
        alphas = []
        for s, y in zip(reversed(self._s_hist), reversed(self._y_hist)):
            rho = 1.0 / jnp.dot(y, s)
            a = rho * jnp.dot(s, q)
            q = q - a * y
            alphas.append((a, rho, s, y))
        if self._s_hist:
            s, y = self._s_hist[-1], self._y_hist[-1]
            q = q * (jnp.dot(s, y) / jnp.dot(y, y))
        for a, rho, s, y in reversed(alphas):
            b = rho * jnp.dot(y, q)
            q = q + (a - b) * s
        direction = -q
        lr = self.get_lr()
        self._prev_flat = flat_w + lr * direction
        self._prev_grad = flat_g
        off = 0
        new_flat = self._prev_flat
        for p in params:
            n = p.size
            p._data = new_flat[off:off + n].reshape(p._data.shape).astype(p.dtype)
            off += n
        return None
