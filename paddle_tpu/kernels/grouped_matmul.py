"""Pallas grouped matrix products over RAGGED groups (TPU): the held
experts of a mixture-of-experts layer, dropless.

Rows arrive sorted by group and laid out so that every group starts on a
row-tile boundary (`ragged_layout`): a row tile then belongs to exactly
one group, and scalar prefetch names each tile's group, so a grid step
multiplies one (tm, tk) block of rows by one (tk, tn) block of THAT
group's matrix. The grid's first bound is the count of LIVE tiles, known
on the device only (a dynamic grid bound, as in the paged decode kernel):
tiles past it are never run, so the product costs what the routed rows
need, at any load, where a masked dense product over E groups costs E
times the rows. A group that received no row has no tile: its weights
are not streamed.

Two kernels: `grouped_swiglu` (silu(x Wg) * (x Wu), both matrices of the
row's group streamed in one pass over x) and `grouped_matmul` (x W). Dots
take the operands' own type (bfloat16 on the chip) and accumulate in
float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_mode

__all__ = ["ragged_layout", "grouped_swiglu", "grouped_matmul",
           "row_tile", "padded_rows"]

_I0 = np.int32(0)


def row_tile(max_rows: int) -> int:
    """Rows a tile: 128 where a launch can bring many rows a group (a
    prefill chunk), 16 (one packed bfloat16 sublane tile) where it brings
    one or two (a decode step: the product is bound by the weights it
    streams, and a taller tile is rows of zeros)."""
    return 128 if max_rows > 1024 else 16


def padded_rows(max_rows: int, groups: int, tm: int) -> int:
    """The static row count that holds `max_rows` rows in `groups` groups
    each padded to whole tiles, whatever the split."""
    return -(-(max_rows + groups * (tm - 1)) // tm) * tm


def _block(n: int, want: int) -> int:
    """Largest block <= want that divides n (n itself where n <= want)."""
    if n <= want:
        return n
    for b in range(want, 0, -1):
        if n % b == 0 and (b % 128 == 0 or b == n):
            return b
    return n


def ragged_layout(group_of_row, groups: int, tm: int, rows_pad: int):
    """Lay rows out by group on tile boundaries.

    group_of_row (M,) int32: each row's group, or `groups` for a row that
    belongs to none (it gets no slot). Returns
      src        (rows_pad,) int32: the row that fills each slot, M for an
                 empty slot (pad the rows with one of zeros and gather);
      slot_of    (M,) int32: each row's slot, rows_pad for a row of no
                 group (pad the product with one row of zeros);
      tile_group (rows_pad // tm,) int32: each tile's group (a dead
                 tile repeats the last live tile's, so that it names the
                 block already held);
      live_tiles () int32;
      sizes      (groups,) int32: rows a group."""
    M = group_of_row.shape[0]
    g = group_of_row.astype(jnp.int32)
    sizes = jnp.zeros((groups + 1,), jnp.int32).at[g].add(1)[:groups]
    order = jnp.argsort(g, stable=True).astype(jnp.int32)     # rows by group
    starts = jnp.cumsum(sizes) - sizes                        # in `order`
    padded = (sizes + (tm - 1)) // tm * tm
    pstarts = jnp.cumsum(padded) - padded
    g_sorted = g[order]
    held = g_sorted < groups
    gs = jnp.minimum(g_sorted, groups - 1)
    rank = jnp.arange(M, dtype=jnp.int32) - starts[gs]
    slot_sorted = jnp.where(held, pstarts[gs] + rank, rows_pad)
    src = jnp.full((rows_pad + 1,), M, jnp.int32).at[slot_sorted].set(
        order, mode="drop")[:rows_pad]
    slot_of = jnp.full((M,), rows_pad, jnp.int32).at[order].set(slot_sorted)
    live_tiles = (jnp.sum(padded) // tm).astype(jnp.int32)
    ends = jnp.cumsum(padded)
    tile_start = jnp.arange(rows_pad // tm, dtype=jnp.int32) * tm
    tile_start = jnp.minimum(tile_start, jnp.maximum(ends[-1] - tm, 0))
    tile_group = jnp.minimum(
        jnp.sum(tile_start[:, None] >= ends[None, :], axis=1,
                dtype=jnp.int32), groups - 1)
    return src, slot_of, tile_group, live_tiles, sizes


def _swiglu_kernel(tg_ref, x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...]
    accg_ref[...] += jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        g = accg_ref[...]
        o_ref[...] = (g * jax.nn.sigmoid(g) * accu_ref[...]).astype(
            o_ref.dtype)


def _matmul_kernel(tg_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped(kernel, name, x, weights, tile_group, live_tiles, tm, n_acc):
    rows, K = x.shape
    E, _, N = weights[0].shape
    if rows % tm:
        raise ValueError(f"{rows} rows are not whole tiles of {tm}")
    tk, tn = _block(K, 1024), _block(N, 512)
    tiles = rows // tm
    # a tail of valid entries: index maps are evaluated past the last step
    tg = jnp.pad(tile_group.astype(jnp.int32), (0, -tiles % 128 + 128),
                 mode="edge")

    def x_map(m, n, k, tg):
        return m, k

    def w_map(m, n, k, tg):
        return tg[m], k, n

    def o_map(m, n, k, tg):
        return m, n

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(live_tiles, N // tn, K // tk),
        in_specs=([pl.BlockSpec((tm, tk), x_map)]
                  + [pl.BlockSpec((1, tk, tn), w_map)] * len(weights)),
        out_specs=pl.BlockSpec((tm, tn), o_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * n_acc,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=_interpret_mode(),
        name=name,
    )(tg, x, *weights)


def grouped_swiglu(x, w_gate, w_up, tile_group, live_tiles, tm):
    """silu(x Wg[g]) * (x Wu[g]) a row tile, g the tile's group. x
    (rows, K) laid out by `ragged_layout`; w_gate, w_up (E, K, N). Rows of
    tiles past `live_tiles` are NOT written: mask them where they are
    read (`ragged_layout`'s `slot_of` never points at one)."""
    return _grouped(_swiglu_kernel, "moe_grouped_matmul_gate_up", x,
                    (w_gate, w_up), tile_group, live_tiles, tm, 2)


def grouped_matmul(x, w, tile_group, live_tiles, tm):
    """x W[g] a row tile. x (rows, K); w (E, K, N)."""
    return _grouped(_matmul_kernel, "moe_grouped_matmul_down", x, (w,),
                    tile_group, live_tiles, tm, 1)
