"""Batched heterogeneous-adapter LoRA matmul (Pallas TPU) — the
multi-LoRA serving delta GEMM (ISSUE 15).

Capability parity: Punica's BGMV / S-LoRA's batched heterogeneous
segment matmul — every row of one decode launch applies ITS OWN
adapter's low-rank delta, delta_b = (x_b @ A[id_b]) @ B[id_b], without
splitting the batch per adapter or recompiling per adapter set.

Shape contract: x (B, H) float rows; adapter_ids (B,) int32 SLOT ids
into the stacked adapter weights A (S, H, R) / B (S, R, N) fp32 (slot 0
is the reserved null adapter — all-zero matrices, so rows without an
adapter contribute an exact 0.0). Per-slot alpha/rank scaling is folded
into the B stack by the caller (serving/lora/runtime.py) BEFORE the
call, so both paths below compute the identical x@A@(B*scale) formula
— the bit-identity contract between the Pallas and XLA routes and
between engines with different loaded-adapter sets.

The Pallas kernel iterates the SLOT axis in the grid and masks rows
whose id differs — each adapter's weights stream through VMEM once per
OUTPUT-BLOCK COLUMN (N/bn of them; one column at the common decode
dims) regardless of how many rows use it, which is the bandwidth-right
shape for decode (B rows, tiny R): a gather-based bmv would re-read a
popular adapter's A/B once per ROW. Masked
accumulation is exact: non-matching slots contribute literal 0.0, and
float addition with 0.0 is the identity, so a row's delta is
bit-identical whatever the other slots hold (the solo-vs-mixed engine
acceptance rests on this).

Block discipline (the round-4 chip lessons, statically checked by
tpu-lint):
  * block picks sized against the A3 VMEM estimator
    (`analysis/vmem.py`) with the true element widths
    (`pick_lora_blocks`);
  * index maps on pinned int32 (`_I0`), never bare literals;
  * bk (the H reduction block) is the LANE dim of the x block and the
    sublane dim of the A block at once -> 128-multiple unless whole-dim;
    bn (the out block) is a lane dim -> 128-multiple unless whole-dim;
  * R and B ride whole-dim blocks (ranks are tiny; the batch is the
    sublane dim of x/out and stays whole);
  * anything the tiling cannot express falls back to the XLA gathered
    bmv composition (`lora_matmul_xla`) — same numerics by the folded-
    scale contract above, none of the weight-stream dedup.
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

__all__ = ["lora_matmul", "lora_matmul_xla", "lora_matmul_supported",
           "pick_lora_blocks", "lora_blockspecs", "lora_delta_bytes"]

_I0 = np.int32(0)

# Search ceilings for the divisor search (the estimator does the exact
# accounting; these just bound the candidates).
_BK_MAX = 2048
_BN_MAX = 2048
# Ranks past this have left "low-rank" territory — the (B, R) scratch
# and (bk, R) A blocks stop being small, and the masked full-stack
# sweep stops being the right shape. Callers fall back to XLA.
MAX_KERNEL_RANK = 256


def _blocks(b, bk, r, bn, x_dtype):
    """(in_blocks, out_blocks, scratch) with TRUE dtypes for the A3
    estimator — x in its own dtype, fp32 A/B stacks, int32 id row,
    fp32 accumulator scratch."""
    xd = str(jnp.dtype(x_dtype))
    in_blocks = [((b, bk), xd),              # x tile
                 ((1, bk, r), "float32"),    # one slot's A tile
                 ((1, r, bn), "float32"),    # one slot's (scaled) B tile
                 ((1, b), "int32")]          # per-row slot ids
    out_blocks = [((b, bn), "float32")]
    scratch = [((b, r), "float32")]          # x @ A[s] accumulator
    return in_blocks, out_blocks, scratch


def _fits(b, bk, r, bn, x_dtype):
    ib, ob, sc = _blocks(b, bk, r, bn, x_dtype)
    return estimate_vmem_bytes(ib, ob, sc) <= VMEM_BUDGET_BYTES


def _divisor_block(dim, cap, step):
    """Largest blk <= cap with dim % blk == 0 and blk % step == 0;
    None when no such tiling exists (whole-dim handled by callers)."""
    blk = (min(dim, cap) // step) * step
    while blk >= step:
        if dim % blk == 0:
            return blk
        blk -= step
    return None


def pick_lora_blocks(B, H, R, N, x_dtype=jnp.float32):
    """VMEM-guarded (bk, bn) for the masked segment-bmm grid, or None
    when no legal tiling fits (callers take the XLA fallback).

    B (batch) and R (rank bucket) always ride whole-dim blocks; only
    the H reduction and the N output dim tile. Same
    shrink-until-it-fits discipline as quant_matmul.pick_quant_blocks."""
    if R > MAX_KERNEL_RANK:
        return None
    bk = H if H <= _BK_MAX else _divisor_block(H, _BK_MAX, 128)
    bn = N if N <= _BN_MAX else _divisor_block(N, _BN_MAX, 128)
    if bk is None or bn is None:
        return None
    while not _fits(B, bk, R, bn, x_dtype):
        # shrink H first (the A-streaming dim), then N, staying on
        # tile-aligned divisors; a dim with no smaller legal divisor
        # cannot shrink further
        for dim, cur in (("k", bk), ("n", bn)):
            if cur <= 128:
                continue
            full = H if dim == "k" else N
            cand = _divisor_block(full, cur // 2, 128)
            if cand is None:
                continue
            if dim == "k":
                bk = cand
            else:
                bn = cand
            break
        else:
            return None            # nothing left to shrink
    return bk, bn


def lora_matmul_supported(B, H, R, N, x_dtype=jnp.float32):
    """True when the Pallas path has a legal VMEM-sized tiling."""
    return pick_lora_blocks(B, H, R, N, x_dtype) is not None


def lora_blockspecs(B, S, H, R, N, x_dtype=jnp.float32):
    """The exact (block_shape, array_shape) pairs the pallas_call below
    constructs, enumerable for the static legality test (same contract
    as paged_attention.paged_blockspecs). None when unsupported."""
    picked = pick_lora_blocks(B, H, R, N, x_dtype)
    if picked is None:
        return None
    bk, bn = picked
    return [((B, bk), (B, H)),            # x
            ((1, bk, R), (S, H, R)),      # A stack
            ((1, R, bn), (S, R, N)),      # (scaled) B stack
            ((1, B), (1, B)),             # slot ids
            ((B, bn), (B, N))]            # out


def lora_delta_bytes(B, H, R, N, S_streamed, x_width=4, bn=None):
    """HBM bytes one launch of the masked kernel streams, per the
    ACTUAL grid iteration order (j outermost, then s, then k — Mosaic
    revisit caching only collapses CONSECUTIVE identical block
    indices): every A tile and the x block re-stream once per output
    block column (nj = N/bn of them), each slot's B column tile and
    the output block stream once per column, plus the delta write.
    The null slot counts — the kernel sweeps every slot in the stack.
    The bench's bytes-true accounting source; with `bn=None` (or a
    single column) this reduces to one pass over everything."""
    nj = 1 if bn is None else max(1, -(-N // bn))
    a_bytes = nj * S_streamed * H * R * 4
    b_bytes = S_streamed * R * N * 4
    x_bytes = nj * S_streamed * B * H * x_width
    return int(a_bytes + b_bytes + x_bytes + B * N * 4)


def _kernel(x_ref, a_ref, b_ref, ids_ref, o_ref, acc_ref, *, nk):
    """acc (B, R) accumulates x @ A[s] over the H blocks; at the last H
    block the slot's delta (acc @ B[s]) lands on the rows whose id
    matches s (others add an exact 0.0). The output block is revisited
    across (s, k) and written first at s == 0, accumulated after."""
    si = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, a_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        mask = (ids_ref[0] == si).astype(jnp.float32)       # (B,)
        contrib = jax.lax.dot_general(
            acc_ref[...], b_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * mask[:, None]

        @pl.when(si == 0)
        def _first():
            o_ref[...] = contrib

        @pl.when(si > 0)
        def _rest():
            o_ref[...] += contrib


def lora_matmul(x2d, adapter_ids, a_stack, b_stack, blocks=None):
    """x2d (B, H) float; adapter_ids (B,) int32 slots; a_stack
    (S, H, R) fp32; b_stack (S, R, N) fp32 with per-slot scaling
    pre-folded -> (B, N) fp32 delta via the masked segment-bmm kernel.
    Callers must check `lora_matmul_supported` first (or pass
    pre-picked `blocks`); unsupported shapes raise — use
    `lora_matmul_xla` for the fallback composition."""
    B, H = x2d.shape
    S, _, R = a_stack.shape
    N = b_stack.shape[2]
    if blocks is None:
        blocks = pick_lora_blocks(B, H, R, N, x2d.dtype)
    if blocks is None:
        raise ValueError(
            f"no VMEM-legal tiling for B={B} H={H} R={R} N={N} — route "
            "through lora_matmul_xla")
    bk, bn = blocks
    nk = H // bk
    grid = (N // bn, S, nk)
    ids_row = adapter_ids.astype(jnp.int32)[None, :]
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, bk), lambda j, s, k: (_I0, k)),
            pl.BlockSpec((1, bk, R), lambda j, s, k: (s, k, _I0)),
            pl.BlockSpec((1, R, bn), lambda j, s, k: (s, _I0, j)),
            # block dims equal the (1, B) array dims (the documented
            # whole-array-dim case A2 cannot see)
            pl.BlockSpec((1, B),  # tpu-lint: blockspec-ok
                         lambda j, s, k: (_I0, _I0)),
        ],
        out_specs=pl.BlockSpec((B, bn), lambda j, s, k: (_I0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((B, R), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret_mode(),
        name="lora_matmul",
        # tpu-lint-hint: vmem-dtypes=float32,float32,float32,int32
    )(x2d, a_stack, b_stack, ids_row)


def lora_matmul_xla(x2d, adapter_ids, a_stack, b_stack):
    """XLA fallback: gather each row's A/B and bmv — the same
    x @ A[id] @ (B*scale)[id] contraction per row (fp32 accumulate,
    row-independent), none of the weight-stream dedup. Used for
    untileable shapes, ranks past MAX_KERNEL_RANK, and multi-token
    rows (prefill chunks)."""
    ids = adapter_ids.astype(jnp.int32)
    a_g = jnp.take(a_stack, ids, axis=0)          # (B, H, R)
    b_g = jnp.take(b_stack, ids, axis=0)          # (B, R, N)
    xa = jnp.einsum("bh,bhr->br", x2d.astype(jnp.float32), a_g)
    return jnp.einsum("br,brn->bn", xa, b_g)
