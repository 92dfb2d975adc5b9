"""Fused multi-tensor AdamW update — one Pallas pass over flat buckets.

An early chip reading put the AdamW update AT the HBM roofline (~21 ms
for 608M fp32 states; not re-measured since): the update is pure bytes, so the only
levers left are (a) narrower state bytes (bf16 moments, already
storable via `moment_dtype="bfloat16"`) and (b) ONE read and ONE write
per state byte instead of the per-parameter upcast/downcast round trips
XLA emits for the eager per-leaf update. This module is lever (b): the
TPU-native rebuild of Paddle's fused_adam multi-tensor kernel
(reference `paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu`, SURVEY
layer 2 — there a single CUDA kernel walks a chunked tensor list; here
the leaves are packed once into padded flat buckets and a single
`pallas_call` streams the bucket).

Geometry (single source: `build_bucket_layout`): every parameter leaf
flattens into one 1-D bucket per update group, zero-padded to a
(rows, 128) view whose rows are 64-aligned — 64 sublanes covers the
fp32(8)/bf16(16)/int8(32) minimum tiles, keeps every block
(8, 128)-legal, and is further aligned to the ZeRO sharding degree so
`P("sharding", None)` always divides. Zero padding is update-invariant:
g = m = v = w = 0 stays 0 through the AdamW expression.

The kernel reads (grad, master-or-param, m, v) blocks and writes
(param[, master], m, v) blocks — every state byte moves exactly once
each way; bias correction, lr, decoupled weight decay arrive via
SCALAR PREFETCH (an fp32 vector in SMEM) so a changing step count never
recompiles the kernel. Block rows are picked against the SAME A3 VMEM
estimator tpu-lint runs (`analysis/vmem.py::fits_vmem`,
`fp32_copies=5` for the g/w/m/v/update fp32 temporaries a block
materializes) — `pick_block_rows_fused` is the off-chip cross-check
anchor for the lint fixtures. Untileable-or-tiny buckets and the
ZeRO-1 path use `_adamw_math` through XLA instead (`use_pallas=False`):
under GSPMD a pallas_call is an opaque custom call the partitioner can
only replicate, while the identical jnp expression partitions exactly —
each 'sharding' rank updates its bucket rows and the replication
constraint on the param output IS the ZeRO-1 all-gather (GSPMD
constraints outside shard_map, per the architecture invariants).

Numerics contract (tests/test_fused_optimizer.py): `_adamw_math` is the
ONLY update expression — the Pallas kernel body and the XLA fallback
both call it, with scalars rounded to fp32 exactly where the eager
per-parameter path's weak-typed python floats round, so fused-vs-eager
is bit-identical for the bf16-moment storage path and byte-exact for
fp32 state.
"""
from __future__ import annotations

import functools
import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _I0, _interpret_mode
from ..analysis.vmem import fits_vmem

__all__ = ["BucketLayout", "build_bucket_layout", "pack_bucket",
           "unpack_bucket", "adamw_scalars", "adamw_update_bytes",
           "pick_block_rows_fused", "fused_adamw_bucket",
           "fused_adamw_zero1", "LANES", "ROW_ALIGN", "PALLAS_MIN_ROWS"]

LANES = 128          # lane width of the 2-D bucket view
ROW_ALIGN = 64       # sublane alignment: covers fp32/bf16/int8 min tiles
# below this many rows a kernel dispatch costs more than the fused read
# saves — the XLA composition (which fuses a small bucket into one
# loop anyway) takes over
PALLAS_MIN_ROWS = 1024
# half of Mosaic's ~16 MB scoped-vmem budget, same headroom policy as
# fused_norm.pick_block_rows
VMEM_TARGET_BYTES = 8 * 1024 * 1024
N_SCALARS = 9        # lr, wd_factor, b1, 1-b1, b2, 1-b2, bc1, bc2, eps


class BucketLayout(NamedTuple):
    """Geometry of one packed bucket — the single source every consumer
    (kernel, XLA fallback, state_dict slicing, bench bytes math) reads.

    entries: tuple of (param_index, flat_offset, size, shape) per leaf;
    rows:    padded row count of the (rows, LANES) bucket view.
    """
    entries: Tuple[Tuple[int, int, int, Tuple[int, ...]], ...]
    rows: int

    @property
    def padded_size(self) -> int:
        return self.rows * LANES

    @property
    def used_size(self) -> int:
        return sum(e[2] for e in self.entries)


def build_bucket_layout(shapes: Sequence[Tuple[int, Tuple[int, ...]]],
                        sharding_degree: int = 1) -> BucketLayout:
    """Layout for leaves [(param_index, shape), ...]: contiguous flat
    offsets, rows padded to lcm(ROW_ALIGN, sharding_degree) so blocks
    stay (8, 128)-legal AND P('sharding', None) divides the rows."""
    entries = []
    off = 0
    for idx, shape in shapes:
        size = int(math.prod(shape)) if shape else 1
        entries.append((int(idx), off, size, tuple(int(d) for d in shape)))
        off += size
    align = math.lcm(ROW_ALIGN, max(1, int(sharding_degree)))
    rows = -(-max(off, 1) // LANES)          # ceil div
    rows = -(-rows // align) * align
    return BucketLayout(tuple(entries), rows)


def pack_bucket(arrays: Sequence[jax.Array], layout: BucketLayout,
                dtype) -> jax.Array:
    """Concatenate leaves (layout order) + zero pad -> (rows, LANES)."""
    flat = [a.reshape(-1).astype(dtype) for a in arrays]
    pad = layout.padded_size - layout.used_size
    if pad:
        flat.append(jnp.zeros((pad,), dtype))
    return jnp.concatenate(flat).reshape(layout.rows, LANES)


def unpack_bucket(bucket: jax.Array, layout: BucketLayout) -> List[jax.Array]:
    """Slice a (rows, LANES) bucket back into leaves (layout order)."""
    flat = bucket.reshape(-1)
    return [flat[off:off + size].reshape(shape)
            for (_, off, size, shape) in layout.entries]


def adamw_scalars(lr: float, beta1: float, beta2: float, eps: float,
                  weight_decay: float, step: int) -> jax.Array:
    """The prefetched scalar vector. Every entry is rounded f64 -> f32
    exactly where the eager path's weak-typed python floats round when
    they meet an fp32 array, so fused and eager round identically."""
    lr = float(lr)
    return jnp.asarray(np.array([
        lr,
        1.0 - lr * float(weight_decay),      # decoupled-decay factor
        beta1, 1.0 - beta1,
        beta2, 1.0 - beta2,
        1.0 - beta1 ** int(step),            # bias correction 1
        1.0 - beta2 ** int(step),            # bias correction 2
        eps,
    ], np.float32))


def adamw_update_bytes(n_elems: int, param_width: int = 4,
                       moment_width: int = 4, has_master: bool = False,
                       grad_width: Optional[int] = None) -> int:
    """Bytes one fused update moves (single-read/single-write contract):
    read grad + (master | param) + m + v, write param (+ master) + m +
    v. The bench_ops optimizer rows and the BASELINE sizing math both
    use this so accounting can never drift from the kernel."""
    gw = param_width if grad_width is None else grad_width
    reads = gw + (4 if has_master else param_width) + 2 * moment_width
    writes = param_width + (4 if has_master else 0) + 2 * moment_width
    return int(n_elems) * (reads + writes)


def _adamw_math(g, w, m, v, lr, wdf, b1, omb1, b2, omb2, bc1, bc2, eps):
    """THE AdamW expression — written token-for-token like the eager
    `AdamW._apply_one` (same association order: `omb2 * g * g` is
    ((omb2*g)*g), `lr * mhat / (...)` is ((lr*mhat)/(...))) so the
    fused paths round bit-identically to the per-parameter path."""
    g = g.astype(jnp.float32)
    w = w.astype(jnp.float32) * wdf
    m = b1 * m.astype(jnp.float32) + omb1 * g
    v = b2 * v.astype(jnp.float32) + omb2 * g * g
    mhat = m / bc1
    vhat = v / bc2
    w = w - lr * mhat / (jnp.sqrt(vhat) + eps)
    return w, m, v


def _adamw_kernel(s_ref, g_ref, w_ref, m_ref, v_ref, *out_refs, has_master):
    w, m, v = _adamw_math(
        g_ref[...], w_ref[...], m_ref[...], v_ref[...],
        s_ref[0], s_ref[1], s_ref[2], s_ref[3], s_ref[4], s_ref[5],
        s_ref[6], s_ref[7], s_ref[8])
    if has_master:
        p_out, w_out, m_out, v_out = out_refs
        p_out[...] = w.astype(p_out.dtype)
    else:
        w_out, m_out, v_out = out_refs
    w_out[...] = w.astype(w_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def pick_block_rows_fused(rows: int, in_dtypes: Sequence[str],
                          out_dtypes: Sequence[str],
                          block_rows: int = 1024,
                          budget: int = VMEM_TARGET_BYTES) -> int:
    """Row-block pick validated against the SAME estimator tpu-lint's
    A3 rule runs: double-buffered (block_rows, LANES) blocks at their
    true widths plus fp32_copies=5 compute temporaries (g, w, m, v and
    the update quotient live as fp32 block-sized values). Halve until
    the estimate fits the budget AND the pick divides the padded rows
    (build_bucket_layout's 64-alignment guarantees a divisor >= 8
    exists for pow-2 candidates)."""
    while True:
        ins = [((block_rows, LANES), str(d)) for d in in_dtypes]
        outs = [((block_rows, LANES), str(d)) for d in out_dtypes]
        ok, _ = fits_vmem(ins, outs, fp32_copies=5, budget=budget)
        if ok:
            break
        if block_rows <= 8:
            raise ValueError(
                "fused optimizer: even an 8-row block exceeds the VMEM "
                "budget — use the XLA fallback for this bucket")
        block_rows //= 2
    while rows % block_rows != 0:
        block_rows //= 2
        if block_rows < 8:
            raise ValueError(
                f"fused optimizer: rows={rows} has no 8-aligned pow-2 "
                "divisor — pad the bucket with build_bucket_layout")
    return block_rows


def fused_adamw_bucket(grads, weights, m, v, scalars, param_dtype=None,
                       use_pallas: Optional[bool] = None,
                       block_rows: int = 1024):
    """One fused AdamW pass over a (rows, LANES) bucket.

    weights is the fp32 master bucket when `param_dtype` names a
    narrower parameter dtype (multi_precision), else the parameter
    bucket itself. Returns (param_new, weights_new, m_new, v_new) in
    their storage dtypes; param_new is weights_new when no master.

    use_pallas=None picks the kernel for buckets >= PALLAS_MIN_ROWS
    rows and the XLA composition below (a tiny bucket's dispatch costs
    more than the fusion saves); ZeRO-1 forces the XLA path (see
    module docstring).
    """
    rows, lanes = grads.shape
    if lanes != LANES:
        raise ValueError(f"bucket lane dim must be {LANES}, got {lanes}")
    has_master = (param_dtype is not None
                  and jnp.dtype(param_dtype) != weights.dtype)
    if use_pallas is None:
        use_pallas = rows >= PALLAS_MIN_ROWS and rows % 8 == 0

    if not use_pallas:
        w_new, m_new, v_new = _adamw_math(
            grads, weights, m, v, scalars[0], scalars[1], scalars[2],
            scalars[3], scalars[4], scalars[5], scalars[6], scalars[7],
            scalars[8])
        w_out = w_new.astype(weights.dtype)
        m_out = m_new.astype(m.dtype)
        v_out = v_new.astype(v.dtype)
        p_out = w_new.astype(param_dtype) if has_master else w_out
        return p_out, w_out, m_out, v_out

    in_dts = [str(a.dtype) for a in (grads, weights, m, v)]
    out_dts = ([str(jnp.dtype(param_dtype))] if has_master else []) + \
        [str(weights.dtype), str(m.dtype), str(v.dtype)]
    br = pick_block_rows_fused(rows, in_dts, out_dts, block_rows)
    spec = pl.BlockSpec((br, LANES), lambda i, s: (i, _I0))
    out_shapes = []
    if has_master:
        out_shapes.append(
            jax.ShapeDtypeStruct((rows, LANES), jnp.dtype(param_dtype)))
    out_shapes += [jax.ShapeDtypeStruct((rows, LANES), weights.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), m.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), v.dtype)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // br,),
        in_specs=[spec] * 4,
        out_specs=[spec] * len(out_shapes),
    )
    outs = pl.pallas_call(
        functools.partial(_adamw_kernel, has_master=has_master),
        grid_spec=grid_spec,
        out_shape=tuple(out_shapes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret_mode(),
        name="fused_adamw",
    )(scalars, grads, weights, m, v)
    if has_master:
        return outs
    w_out, m_out, v_out = outs
    return w_out, w_out, m_out, v_out


def fused_adamw_zero1(grads, weights, m, v, scalars, mesh,
                      param_dtype=None, axis: str = "sharding"):
    """ZeRO-1 over the SAME bucket layout: moments + master rows
    sharded over the mesh's 'sharding' axis, each rank updates its
    shard, and the replication constraint on the param output is the
    bf16-delta all-gather. GSPMD constraints only — no shard_map (the
    architecture invariant); the update itself is the XLA composition
    so the partitioner can actually split it (a pallas custom call it
    could only replicate)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(axis, None))
    repl = NamedSharding(mesh, P(None, None))

    def constrain(arr, s):
        # under tracing only with_sharding_constraint actually pins the
        # layout (an in-trace device_put is a no-op on this jax);
        # eagerly with_sharding_constraint is unavailable, so place
        # for real (same split as distributed/sharding.py's _place)
        if isinstance(arr, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(arr, s)
        return jax.device_put(arr, s)

    grads = constrain(grads, shard)
    weights = constrain(weights, shard)
    m = constrain(m, shard)
    v = constrain(v, shard)
    p_new, w_new, m_new, v_new = fused_adamw_bucket(
        grads, weights, m, v, scalars, param_dtype=param_dtype,
        use_pallas=False)
    p_new = constrain(p_new, repl)
    # pin the state outputs too: under jit the replicated param output
    # would otherwise win sharding propagation and the compiled step
    # would silently re-replicate the very bytes ZeRO-1 shards
    w_new = constrain(w_new, shard)
    m_new = constrain(m_new, shard)
    v_new = constrain(v_new, shard)
    return p_new, w_new, m_new, v_new
