"""Fused weight-only int8 dequant-matmul (Pallas TPU) — the decode-path
GEMM (ISSUE 6).

Capability parity: the reference's `weight_only_linear` phi kernel
(`paddle/phi/kernels/weight_only_linear_kernel.h`, CUTLASS
mixed-dtype GEMM underneath); rebuilt as a native Pallas kernel that
streams int8 weight blocks into VMEM, converts to fp32 THERE, and
applies the per-output-channel scale once at the accumulator flush —
so the weight's HBM traffic is 1 byte/element instead of 2 (bf16),
which is the entire win in the decode regime where M is tiny and the
GEMM is weight-bandwidth-bound (bench_ops `weight_only_matmul` carries
the measured int8-vs-bf16 decision sweep; the serving engine's
`wq="int8"` config routes the LM head + MLP projections here).

Block discipline (the round-4 on-chip lessons, all statically checked
by tpu-lint):
  * block picks are sized against the A3 VMEM estimator
    (`analysis/vmem.py::estimate_vmem_bytes`) with the TRUE element
    widths — int8 weight blocks, fp32 x/scale blocks — instead of a
    hardcoded table (`pick_quant_blocks`; the rms block_rows=256 OOM
    is the cautionary tale);
  * index maps use pinned int32 (`_I0`), never bare literals (the
    package enables x64 — bare ints trace as i64 and fail Mosaic
    legalization on chip);
  * int8's (32, 128) minimum tile binds strict sub-blocks, so the K
    block is a multiple of 32 unless it spans the whole K dim (the
    whole-dim escape every Mosaic tiling rule grants);
  * anything the tiling cannot express falls back to the XLA
    dequant+matmul composition — same numerics, no Pallas.

`weight_only_linear` (nn/quant) routes its int8 fast path here; this
module keeps the raw-array kernel so the serving engine, bench_ops and
chip_parity can hit it without Tensor plumbing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.vmem import estimate_vmem_bytes, VMEM_BUDGET_BYTES
from .flash_attention import _interpret_mode

__all__ = ["quant_matmul", "quant_matmul_supported", "pick_quant_blocks",
           "quant_matmul_blockspecs", "dequant_matmul_xla"]

_I0 = np.int32(0)

# Search ceilings: one (bm, bk) x block + (bk, bn) int8 block + (bm, bn)
# fp32 accumulator must fit scoped VMEM with double-buffered DMA; the
# estimator does the exact accounting below, these just bound the
# divisor search.
_BM_MAX = 256
_BK_MAX = 1024
_BN_MAX = 1024


def _blocks(bm, bk, bn, x_dtype):
    """(in_blocks, out_blocks, scratch) with TRUE dtypes for the A3
    estimator — int8 weight block, fp32 scale row, x in its own dtype,
    fp32 accumulator scratch."""
    xd = str(jnp.dtype(x_dtype))
    in_blocks = [((bm, bk), xd),           # x tile
                 ((bk, bn), "int8"),       # quantized weight tile
                 ((1, bn), "float32")]     # per-out-channel scales
    out_blocks = [((bm, bn), xd)]
    scratch = [((bm, bn), "float32")]      # accumulator
    return in_blocks, out_blocks, scratch


def _fits(bm, bk, bn, x_dtype):
    ib, ob, sc = _blocks(bm, bk, bn, x_dtype)
    # fp32_copies=2 models the int8->fp32 weight upcast + the fp32 x
    # copy the MXU path materializes per block (same accounting the
    # rms kernel's chip OOM validated)
    return estimate_vmem_bytes(ib, ob, sc) <= VMEM_BUDGET_BYTES


def _divisor_block(dim, cap, step):
    """Largest b <= cap with dim % b == 0 and b % step == 0; None when
    no such tiling exists (the whole-dim case is handled by callers)."""
    b = (min(dim, cap) // step) * step
    while b >= step:
        if dim % b == 0:
            return b
        b -= step
    return None


def pick_quant_blocks(M, K, N, x_dtype=jnp.float32):
    """VMEM-guarded (bm, bk, bn) for the dequant-matmul grid, or None
    when no legal tiling fits (callers take the XLA fallback).

    Discipline mirrors fused_norm.pick_block_rows: start from the
    bandwidth-friendly targets, shrink (halving via the divisor search)
    until the A3 estimate fits the scoped-VMEM budget. Legality per
    dim: whole-dim blocks are always legal; strict sub-blocks need
    bm%8==0 (sublanes), bn%128==0 (lanes), and bk%128==0 — bk is the
    LANE dim of the x block and the sublane dim of the int8 weight
    block at once, so it must satisfy both (128 covers int8's 32-row
    sublane tile)."""
    bm = M if M <= _BM_MAX else _divisor_block(M, _BM_MAX, 8)
    bk = K if K <= _BK_MAX else _divisor_block(K, _BK_MAX, 128)
    bn = N if N <= _BN_MAX else _divisor_block(N, _BN_MAX, 128)
    if bm is None or bk is None or bn is None:
        return None
    # strict sub-blocks must respect the dtype tiles even when the dim
    # itself is small but not tileable (e.g. K=48 with bk=48 is the
    # whole dim -> fine; K=1040 with bk=520 is not a 32-multiple -> the
    # divisor search above already guarantees it is)
    while not _fits(bm, bk, bn, x_dtype):
        # shrink K first (the weight-streaming dim), then N, then M,
        # staying on tile-aligned divisors throughout; a dim that has
        # no smaller legal divisor simply can't shrink further
        for dim, cur, floor, step in (("k", bk, 128, 128),
                                      ("n", bn, 128, 128),
                                      ("m", bm, 8, 8)):
            if cur <= floor:
                continue
            full = {"k": K, "n": N, "m": M}[dim]
            cand = _divisor_block(full, cur // 2, step)
            if cand is None:
                continue
            if dim == "k":
                bk = cand
            elif dim == "n":
                bn = cand
            else:
                bm = cand
            break
        else:
            return None            # nothing left to shrink: no legal pick
    return bm, bk, bn


def quant_matmul_supported(M, K, N, x_dtype=jnp.float32):
    """True when the Pallas path has a legal VMEM-sized tiling."""
    return pick_quant_blocks(M, K, N, x_dtype) is not None


def quant_matmul_blockspecs(M, K, N, x_dtype=jnp.float32):
    """The exact (block_shape, array_shape) pairs the pallas_call below
    constructs, enumerable for the static legality test (same contract
    as paged_attention.paged_blockspecs). None when unsupported."""
    picked = pick_quant_blocks(M, K, N, x_dtype)
    if picked is None:
        return None
    bm, bk, bn = picked
    return [((bm, bk), (M, K)),        # x
            ((bk, bn), (K, N)),        # int8 weight
            ((1, bn), (1, N)),         # scales
            ((bm, bn), (M, N))]        # out


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk):
    """acc[m, n] += x[m, k] @ f32(w_int8[k, n]); the per-out-channel
    scale multiplies ONCE at the flush — mathematically identical to
    scaling the dequantized weight (scales are per column), one fewer
    VMEM-wide multiply per K step."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)             # int8 -> f32 in VMEM
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * s_ref[0][None, :]).astype(o_ref.dtype)


def quant_matmul(x2d, qw, scale, blocks=None):
    """x2d (M, K) float @ dequant(qw (K, N) int8, scale (N,)) -> (M, N)
    in x2d's dtype, via the fused Pallas kernel. Callers must check
    `quant_matmul_supported` first (or pass pre-picked `blocks`);
    unsupported shapes raise — use `dequant_matmul_xla` for the
    fallback composition."""
    M, K = x2d.shape
    N = qw.shape[1]
    if blocks is None:
        blocks = pick_quant_blocks(M, K, N, x2d.dtype)
    if blocks is None:
        raise ValueError(
            f"no VMEM-legal tiling for ({M}, {K}) x ({K}, {N}) — route "
            "through dequant_matmul_xla")
    bm, bk, bn = blocks
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (_I0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret_mode(),
        name="quant_matmul",
        # tpu-lint-hint: vmem-dtypes=float32,int8,float32
    )(x2d, qw, scale[None, :].astype(jnp.float32))


def dequant_matmul_xla(x2d, qw, scale):
    """XLA fallback: materialize the fp32 weight and matmul — same
    numerics as the kernel (fp32 accumulate, scale per out channel),
    none of the bandwidth win. Used off-TPU-tiling shapes and as the
    parity reference in tests/chip_parity."""
    wf = qw.astype(jnp.float32) * scale[None, :].astype(jnp.float32)
    return (x2d.astype(jnp.float32) @ wf).astype(x2d.dtype)
