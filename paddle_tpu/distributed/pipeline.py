"""In-graph pipeline parallelism over the 'pipe' mesh axis.

Parity: reference pipeline runtime — micro-batch schedules
(`fleet/meta_parallel/pipeline_parallel.py:565` 1F1B, `:1161` interleave /
virtual pipeline, static passes `passes/pipeline_scheduler_pass/`) and the
P2P layer (`pp_utils/p2p_communication.py` batched isend/irecv).

TPU-native: there is no host-driven micro-step loop with NCCL p2p. The
whole schedule is one compiled XLA program: stage weights are stacked on a
leading dim sharded over 'pipe'; a lax.scan over ticks moves activations
between neighbor stages with lax.ppermute (ICI neighbor exchange — the
send_v2/recv_v2 analog); jax AD differentiates the scan, so the backward
pipeline (reverse ppermute chain) is derived, not hand-scheduled. Memory is
controlled with jax.checkpoint per stage (the reference needs 1F1B for
this; remat-in-scan achieves the same peak-activation bound, with the
schedule left to the XLA scheduler).

The shard_map is *partial-manual*: only the pipe axis is manual
(`axis_names={'pipe'}`); every other hybrid axis (data/model/sep/sharding)
stays automatic, so GSPMD tensor-parallel sharding constraints inside a
stage body keep working — pp composes with tp/dp/sp in one program.

Interleaved (virtual-pipeline) schedule: with ``n_virtual > 1`` each device
owns ``n_virtual`` non-adjacent layer chunks (chunk c lives at device
``c % n_stages``, round ``c // n_stages``), and micro-batches circulate the
device ring ``n_virtual`` times — the circular schedule of the reference's
`PipelineParallelWithInterleave` (`pipeline_parallel.py:1161`). Micro-batches
are processed in groups of ``n_stages``; per group the bubble shrinks from
``(n_stages-1)`` full-stage slots to ``(n_stages-1)`` chunk slots (a
``1/n_virtual`` reduction, the interleave payoff).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["pipeline_forward", "stack_stage_params", "PipelineMicroScheduler"]

PIPE_AXIS = "pipe"


def stack_stage_params(per_stage_params, n_virtual: int = 1):
    """List (len n_stages*n_virtual, chunk-major: chunk c = v*n_stages + d)
    of identical-structure pytrees -> stacked pytree. Leaves gain a leading
    (n_stages, ...) dim for n_virtual == 1, or (n_virtual, n_stages, ...)
    dims otherwise; the stage dim is sharded over 'pipe'."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0),
                                     *per_stage_params)
    if n_virtual == 1:
        return stacked
    n_chunks = len(per_stage_params)
    n_stages = n_chunks // n_virtual
    return jax.tree_util.tree_map(
        lambda a: a.reshape(n_virtual, n_stages, *a.shape[1:]), stacked)


def pipeline_forward(stage_params, micro_inputs, stage_fn: Callable, mesh,
                     axis: str = PIPE_AXIS, remat: bool = True,
                     extras=(), n_virtual: int = 1):
    """Run `stage_fn(params, x, *extras) -> y` as a pipeline over `axis`.

    stage_params: pytree; leaves (n_stages, ...) — or, when n_virtual > 1,
        (n_virtual, n_stages, ...) — sharded over `axis` on the stage dim.
    micro_inputs: (n_micro, *mb_shape) — replicated over `axis` (stage 0
        consumes them; ppermute forwards activations down the chain).
    extras: arrays passed unchanged to every stage invocation (e.g. rope
        tables), replicated over `axis`.
    Returns (n_micro, *mb_shape) outputs of the final chunk, replicated
    over `axis` (zero-padded contributions psum-gathered).

    Differentiable end-to-end: jax.grad of a loss on the returned outputs
    yields the reverse pipeline automatically.
    """
    if n_virtual > 1:
        return _pipeline_circular(stage_params, micro_inputs, stage_fn, mesh,
                                  axis, remat, extras, n_virtual)
    n_stages = mesh.shape[axis]
    n_micro = micro_inputs.shape[0]
    total_ticks = n_micro + n_stages - 1

    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    extra_specs = tuple(P() for _ in extras)

    def per_device(params, xs, *ex):
        # params leaves: (1, ...) — this device's stage; squeeze lead dim
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage_id = jax.lax.axis_index(axis)

        def fn_(p, x):
            return stage_fn(p, x, *ex)

        fn = jax.checkpoint(fn_) if remat else fn_

        def tick(buf, t):
            # stage 0 consumes microbatch t (clamped); others take the buffer
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            mb = jax.lax.dynamic_index_in_dim(xs, mb_idx, axis=0,
                                              keepdims=False)
            x_in = jnp.where(stage_id == 0, mb, buf)
            y = fn(params, x_in)
            # last stage's finished microbatch (zeros elsewhere / off-window)
            done = jnp.logical_and(stage_id == n_stages - 1,
                                   jnp.logical_and(t >= n_stages - 1,
                                                   t < total_ticks))
            out = jnp.where(done, y, jnp.zeros_like(y))
            # neighbor exchange: stage i -> i+1 (last stage sends nowhere;
            # ring perm keeps the collective uniform, stage 0 overwrites)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf_next = jax.lax.ppermute(y, axis, perm)
            return buf_next, out

        buf0 = jnp.zeros_like(jax.eval_shape(fn, params, xs[0]))
        _, outs = jax.lax.scan(tick, buf0, jnp.arange(total_ticks))
        # outs: (total_ticks, *mb) — microbatch m finished at tick m+n_stages-1
        outs = outs[n_stages - 1:]
        # replicate final-stage results to every pipe rank (others hold 0)
        outs = jax.lax.psum(outs, axis)
        return outs

    mapped = shard_map(per_device, mesh=mesh,
                       in_specs=(param_specs, P()) + extra_specs,
                       out_specs=P(),
                       axis_names={axis},
                       check_vma=False)
    # partial-manual shard_map (manual 'pipe', auto tp/dp axes) only traces
    # under jit; inlined for free when an outer jit (to_static) is active
    return jax.jit(mapped)(stage_params, micro_inputs, *extras)


def _pipeline_circular(stage_params, micro_inputs, stage_fn, mesh, axis,
                       remat, extras, n_virtual):
    """Interleaved (circular / virtual-pipeline) schedule.

    Chunk c = v*n_stages + d runs at device d on ring pass v. Micro-batch m
    of a group enters chunk (v, device d) at tick m + v*n_stages + d; per
    tick every device computes at most one (microbatch, chunk) pair —
    ``u = t - stage_id``, valid iff 0 <= u < n_stages*n_virtual, with
    v = u // n_stages and local microbatch m = u % n_stages. Micro-batches
    run in groups of n_stages (the in-flight window of the circular
    schedule); one lax.scan covers all groups.
    """
    n = mesh.shape[axis]
    V = n_virtual
    n_micro = micro_inputs.shape[0]
    if n_micro % n != 0:
        raise ValueError(
            f"interleaved pipeline needs n_micro ({n_micro}) divisible by "
            f"n_stages ({n})")
    n_groups = n_micro // n
    group_ticks = n * V + n - 1
    total_ticks = n_groups * group_ticks

    param_specs = jax.tree_util.tree_map(lambda _: P(None, axis), stage_params)
    extra_specs = tuple(P() for _ in extras)

    def per_device(params, xs, *ex):
        # leaves (V, 1, ...) -> (V, ...): this device's V chunks
        params = jax.tree_util.tree_map(lambda a: a[:, 0], params)
        stage_id = jax.lax.axis_index(axis)

        def fn_(p, x):
            return stage_fn(p, x, *ex)

        fn = jax.checkpoint(fn_) if remat else fn_
        p0 = jax.tree_util.tree_map(lambda a: a[0], params)
        mb_shape = jax.eval_shape(fn, p0, xs[0])

        def tick(buf, t):
            g = t // group_ticks
            tl = t % group_ticks           # tick within the group
            u = tl - stage_id              # chunk-progress index
            v = jnp.clip(u // n, 0, V - 1)
            m_local = jnp.clip(u, 0, n * V - 1) % n
            m = jnp.clip(g * n + m_local, 0, n_micro - 1)
            mb = jax.lax.dynamic_index_in_dim(xs, m, axis=0, keepdims=False)
            # device 0 takes a fresh microbatch on ring pass 0 only; later
            # passes consume the buffer arriving from device n-1
            fresh = jnp.logical_and(stage_id == 0,
                                    jnp.logical_and(u >= 0, u < n))
            x_in = jnp.where(fresh, mb, buf)
            pv = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v, axis=0,
                                                       keepdims=False),
                params)
            y = fn(pv, x_in)
            # last device on the last ring pass emits finished microbatches
            done = jnp.logical_and(
                stage_id == n - 1,
                jnp.logical_and(u >= n * (V - 1), u < n * V))
            out = jnp.where(done, y, jnp.zeros_like(y))
            perm = [(i, (i + 1) % n) for i in range(n)]
            return jax.lax.ppermute(y, axis, perm), out

        buf0 = jnp.zeros_like(mb_shape)
        _, outs = jax.lax.scan(tick, buf0, jnp.arange(total_ticks))
        # per group, the final n ticks emit microbatches g*n .. g*n + n - 1
        outs = outs.reshape(n_groups, group_ticks, *outs.shape[1:])[:, -n:]
        outs = outs.reshape(n_micro, *outs.shape[2:])
        return jax.lax.psum(outs, axis)

    mapped = shard_map(per_device, mesh=mesh,
                       in_specs=(param_specs, P()) + extra_specs,
                       out_specs=P(),
                       axis_names={axis},
                       check_vma=False)
    return jax.jit(mapped)(stage_params, micro_inputs, *extras)


ZB_SCHEDULES = ("ZB-H1", "ZB", "zero_bubble")
# ZB composed with the 2-chunk virtual pipeline (V placement). Kept
# separate from ZB_SCHEDULES: consumers that only know the flat H1
# ordering must fail loudly on these, not silently run H1 under a V name
# (fleet_executor.build_zbv_rank_schedules owns the V machinery).
ZBV_SCHEDULES = ("ZB-V", "ZBV")


class PipelineMicroScheduler:
    """Host-level micro-batch scheduler used by fleet.PipelineParallel for
    the eager path (schedule bookkeeping parity: FThenB / 1F1B orderings).
    The compiled path above is the performance path."""

    def __init__(self, n_stages, n_micro, schedule="1F1B"):
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.schedule = schedule

    def steps(self):
        """Yields ('F', i) / ('B', i) — plus ('W', i) for ZB-H1 — events in
        schedule order for rank-0 semantics (single-process SPMD runs the
        whole graph)."""
        if self.schedule == "FThenB":
            for i in range(self.n_micro):
                yield ("F", i)
            for i in range(self.n_micro):
                yield ("B", i)
            return
        if self.schedule in ZB_SCHEDULES or self.schedule in ZBV_SCHEDULES:
            # Host-sequential event view: the B/W split is identical for
            # flat ZB-H1 and chunked ZB-V (the V placement changes which
            # RANK owns which virtual stage — build_zbv_rank_schedules —
            # not the single-process topological order).
            yield from self._zb_h1_steps()
            return
        # n_stages=1 has no pipeline overlap: warmup must still cover
        # F(0) or the steady loop would emit B(0) before its forward
        warmup = min(max(self.n_stages - 1, 1), self.n_micro)
        for i in range(warmup):
            yield ("F", i)
        fwd = warmup
        bwd = 0
        while bwd < self.n_micro:
            if fwd < self.n_micro:
                yield ("B", bwd)
                bwd += 1
                yield ("F", fwd)
                fwd += 1
            else:
                yield ("B", bwd)
                bwd += 1

    def _zb_h1_steps(self):
        """ZB-H1 zero-bubble ordering (parity: reference
        passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:62): the
        backward splits into B (input grads — on the critical path, sent
        upstream immediately) and W (weight grads — free to slide into
        bubbles). Warmup forwards as 1F1B; steady state interleaves F/B;
        W fills the cooldown slots that 1F1B leaves idle, deferring all
        remaining W to the tail."""
        warmup = min(max(self.n_stages - 1, 1), self.n_micro)
        for i in range(warmup):
            yield ("F", i)
        fwd = warmup
        bwd = 0
        w_done = 0
        while bwd < self.n_micro:
            yield ("B", bwd)
            bwd += 1
            if fwd < self.n_micro:
                yield ("F", fwd)
                fwd += 1
            elif w_done < bwd - 1:
                # cooldown bubble: retire a deferred weight grad
                yield ("W", w_done)
                w_done += 1
        while w_done < self.n_micro:
            yield ("W", w_done)
            w_done += 1
