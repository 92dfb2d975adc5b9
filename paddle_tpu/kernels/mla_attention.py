"""Pallas decode attention over a paged LATENT cache (multi-head latent
attention, DeepSeek-V2/V3's MLA, in its absorbed form).

The cache holds ONE entry a token a layer: the normed latent `c_kv`
(width R, 512 as published) followed by the roped shared key `k_pe`
(width Dr, 64), padded with zeros to whole 128-lane tiles
(`mla_entry_width`: 640 for the published 576): `(num_pages, page_size,
W)`. Every query head attends over the same entry, so K and V are views
of the same bytes: the key is the whole entry, the value its first R
lanes. Why the padding (PR 31, v5e): the chip's own layout for a bfloat16
`(16384, 16, 576)` array puts the PAGE axis minor-most (576 is not whole
lane tiles, 16384 is), so every decode step copied each layer's whole
pool into page-major order for the kernel and back (2.94 ms a layer
against 0.96 at width 640, where the layout is page-major and nothing is
copied). The 64 lanes cost a ninth more pool; what a step REQUIRES stays
R + Dr values a token (`benchmarks/families/kimi_k2.py` counts those).
The caller absorbs `kv_b_proj` into the query (`q_nope W^K` per head) and
the output (`P c_kv` through `W^V`), so the kernel sees H query rows of
the entry's width a sequence and returns H rows of width R.

Design (the page-16 lessons of `paged_attention.py`, PR 28): the block
table and lengths ride scalar prefetch; the grid is the flat list of the
rows' LIVE steps; a step computes on a token tile of T = fold * page_size
tokens: one (H, R + Dr) x (R + Dr, T) dot, one online-softmax update on
lane-dense (H, T) scores, one (H, T) x (T, R) dot. The kernel gathers a
tile's `fold` pages itself: the pool stays in HBM (`pl.ANY`), and step w
starts the copies of step w + 1's pages into the other half of a two-tile
VMEM buffer (the steps run in order, so the next step may be the next
row's first) and waits on its own. Why not a BlockSpec a page: the
pipeline pays an index map, a compare and a copy's start and wait for
every block, 80 ns for a 16-token page, 0.87 ms a layer at the serving
cell's call (v5e), where moving the page takes 25. Dead slots of a row's
last tile name page 0, the pad page: they are copied and masked. H heads
share each entry's 2 x (R + Dr) bytes (1,152 as published): 2 H (2 R +
Dr) / (2 (R + Dr)) = 121 FLOP a byte at H 64 against a v5e's ridge of
240, so bandwidth and the MXU sit side by side: the dots take the
cache's own type (bfloat16 on the chip: an fp32 upcast as in the GQA
kernel would make it compute-bound) and accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_mode
from .paged_attention import NEG_INF, _STATS_LANES, _live_steps

__all__ = ["mla_paged_decode", "mla_paged_write", "mla_page_bytes",
           "mla_entry_width", "check_supported_mla", "mla_fold_pages"]

_I0 = np.int32(0)
# A step's token tile, the faster of 256 and 512 at the serving cell's call
# with the kernel's own copies: 0.642 / 0.487 ms (v5e). Each step
# costs about 0.5 us however few tokens it holds; a row's last tile costs
# the bytes of its dead slots.
_TILE_TOKENS = 512


def mla_entry_width(latent_rank, rope_dim) -> int:
    """Lanes of one cache entry: latent_rank + rope_dim values in whole
    128-lane tiles (the module docstring says why)."""
    return -(-(int(latent_rank) + int(rope_dim)) // 128) * 128


def mla_page_bytes(page_size, width, dtype) -> int:
    """HBM bytes of one page of one layer: page_size entries of `width`
    values (K and V are views of them, so they are counted once)."""
    return int(page_size) * int(width) * jnp.dtype(dtype).itemsize


def check_supported_mla(num_heads, latent_rank, rope_dim, page_size, dtype):
    """Static validation mirroring what Mosaic accepts (raise here, with
    a clear message, not deep inside lowering)."""
    if latent_rank % 128:
        raise ValueError(f"kv_lora_rank {latent_rank} must be a multiple "
                         "of 128 (the value is a lane-aligned view of the "
                         "entry)")
    if rope_dim % 2:
        raise ValueError(f"qk_rope_head_dim {rope_dim} must be even")
    if page_size % 16:
        raise ValueError(f"page_size {page_size} must be a multiple of 16 "
                         "(bfloat16 sublane tiling)")
    if num_heads % 8:
        raise ValueError(f"num_attention_heads {num_heads} must be a "
                         "multiple of 8")
    if str(jnp.dtype(dtype)) not in ("bfloat16", "float32"):
        raise ValueError(f"unsupported dtype {dtype} (TPU-native kernels "
                         "accept bfloat16/float32)")


def mla_fold_pages(page_size, max_pages) -> int:
    """Pages gathered per grid step: a `_TILE_TOKENS` tile, clamped to
    the table (the kernel and the legality test share this rule)."""
    return max(1, min(max(_TILE_TOKENS, page_size) // page_size, max_pages))


def _mla_decode_kernel(slots_ref, first_ref, sl_ref, row_ref, step_ref,
                       q_ref, cache_ref, o_ref, buf_ref, sem_ref, acc_ref,
                       m_ref, l_ref, *, sm_scale, page_size, fold, rank):
    w = pl.program_id(0)
    b = row_ref[w]
    i = step_ref[w]
    sl = sl_ref[b]
    tile_tokens = fold * page_size
    half = jax.lax.rem(w, np.int32(2))

    def rows(into, f):
        """Page f's rows of half `into` of the buffer."""
        return buf_ref.at[into, pl.ds(f * page_size, page_size)]

    def gather(step, into):
        """Start the `fold` copies of flat step `step`'s pages into half
        `into` of the buffer, one after another along the token axis."""
        first = first_ref[step]
        for f in range(fold):
            pltpu.make_async_copy(cache_ref.at[slots_ref[first + f]],
                                  rows(into, f), sem_ref.at[into]).start()

    @pl.when(w == 0)
    def _first():
        gather(w, half)

    # the steps run in order, so the next step's pages (of this row or
    # the next) stream in while this one computes
    @pl.when(w + 1 < pl.num_programs(0))
    def _ahead():
        gather(w + 1, 1 - half)

    for f in range(fold):     # a wait counts its destination's bytes
        pltpu.make_async_copy(cache_ref.at[_I0], rows(half, f),
                              sem_ref.at[half]).wait()

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = buf_ref[half]                              # (T, R + Dr)
    q = q_ref[0]                                      # (H, R + Dr)
    s = jax.lax.dot_general(q, tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * np.float32(sm_scale)                      # (H, T)
    pos = i * tile_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < sl, s, NEG_INF)
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
    l_ref[...] = jnp.broadcast_to(
        l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(tile.dtype), tile[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((i + 1) * tile_tokens >= sl)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], np.float32(1e-30))
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def mla_paged_decode(q, cache, block_tables, seq_lens, *, rank, sm_scale):
    """One decode step of absorbed latent attention over the paged cache.

    q:            (B, H, W), the absorbed queries `[q_nope W^K | q_pe]`
                  padded with zeros to the entry's width.
    cache:        (num_pages, page_size, W), W = `mla_entry_width`.
    block_tables: (B, max_pages) int32; unused slots hold a valid page id
                  (0, the pad page: masked by seq_lens).
    seq_lens:     (B,) int32, live tokens a row, the one being decoded
                  included.
    Returns (B, H, R) float32: softmax(q K^T * sm_scale) @ c_kv.
    """
    B, H, W = q.shape
    num_pages, page_size, Wc = cache.shape
    if W != Wc:
        raise ValueError(f"query width {W} != cache entry width {Wc}")
    if W % 128:
        raise ValueError(f"entry width {W} is not whole 128-lane tiles "
                         "(mla_entry_width)")
    check_supported_mla(H, rank, 2, page_size, cache.dtype)
    return _mla_decode_once(q, cache, block_tables, seq_lens, rank=rank,
                            sm_scale=float(sm_scale),
                            interpret=_interpret_mode())


@functools.partial(jax.jit, static_argnames=("rank", "sm_scale", "interpret"))
def _mla_decode_once(q, cache, block_tables, seq_lens, *, rank, sm_scale,
                     interpret):
    """`mla_paged_decode`'s call, traced and lowered ONCE a program however
    many layers make it, as `flash_attention._fwd_positions_once` is: the
    decode program's seven calls lower in 0.17 s for a described v5e, and
    in 1.10 s without the inner jit (a BlockSpec a page lowered in 0.60:
    the copies make the kernel's body longer)."""
    B, H, W = q.shape
    page_size = cache.shape[1]
    max_pages = block_tables.shape[1]
    bt = block_tables.astype(jnp.int32)
    sl = jnp.minimum(seq_lens.astype(jnp.int32), max_pages * page_size)
    fold = mla_fold_pages(page_size, max_pages)
    if max_pages % fold:
        pad = fold - max_pages % fold
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
        max_pages += pad
    total, row_of, step_of, first_of = _live_steps(
        sl, fold * page_size, max_pages // fold, fold)
    # every scalar-prefetch array ends in 128+ valid zero words: the index
    # maps are evaluated past the last step (paged_attention.py, PR 28)
    prefetch = [jnp.pad(a, (0, -a.shape[0] % 128 + 128))
                for a in (bt.reshape(-1), first_of, sl, row_of, step_of)]

    def row_block(w, slots, first_of, sl, row_of, step_of):
        return row_of[w], _I0, _I0

    kernel = functools.partial(_mla_decode_kernel, sm_scale=sm_scale,
                               page_size=page_size, fold=fold, rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(total,),
        in_specs=[pl.BlockSpec((1, H, W), row_block),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, rank), row_block),
        scratch_shapes=[pltpu.VMEM((2, fold * page_size, W), cache.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((H, rank), jnp.float32),
                        pltpu.VMEM((H, _STATS_LANES), jnp.float32),
                        pltpu.VMEM((H, _STATS_LANES), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_paged_decode",
    )(*prefetch, q.astype(cache.dtype), cache)


def mla_paged_write(cache, entries, block_tables, lengths, starts):
    """Scatter spans of latent entries into the paged cache: row b's
    `entries[b, j]` lands at token position starts[b] + j for j <
    lengths[b]; dead positions (bucket padding, padded rows) route to
    page 0, the pad page no read sees un-masked, or fall out of bounds
    and are dropped. One pure-XLA scatter for a decode step (S = 1), a
    verify span and a prefill chunk (B = 1) alike.

    cache (num_pages, page_size, W); entries (B, S, <= W), padded with
    zeros to W; block_tables (B, max_pages); lengths, starts (B,)."""
    num_pages, page_size, W = cache.shape
    B, S = entries.shape[:2]
    entries = jnp.pad(entries, ((0, 0), (0, 0), (0, W - entries.shape[-1])))
    P = block_tables.shape[1]
    t = jnp.arange(S, dtype=jnp.int32)[None, :]
    live = t < jnp.asarray(lengths, jnp.int32)[:, None]
    pos = t + jnp.asarray(starts, jnp.int32)[:, None]
    page_idx = jax.lax.div(pos, jnp.int32(page_size))
    page_off = jax.lax.rem(pos, jnp.int32(page_size))
    pages = jnp.where(
        live, jnp.take_along_axis(block_tables.astype(jnp.int32),
                                  jnp.clip(page_idx, 0, P - 1), axis=1), 0)
    # dead positions: page 0 at an offset past the page (dropped), so the
    # pad page keeps its zeros
    page_off = jnp.where(live, page_off, page_size)
    idx = jnp.stack([pages, page_off], axis=-1).reshape(B * S, 2)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1))
    return jax.lax.scatter(
        cache, idx, entries.reshape(B * S, W).astype(cache.dtype), dnums,
        indices_are_sorted=False, unique_indices=False,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
