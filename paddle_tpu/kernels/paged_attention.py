"""Pallas paged-KV-cache decode attention (TPU).

Capability parity: the reference serving kernel pack —
`block_multi_head_attention` (paged KV cache,
`paddle/phi/kernels/fusion/gpu/block_multi_head_attention.cu` via
`python/paddle/incubate/nn/functional/block_multihead_attention.py`) and
`masked_multihead_attention` (decode MHA,
`paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu`).
Rebuilt as a native Pallas TPU kernel over a TPU-friendly page layout
rather than a CUDA translation.

Design:
  * the KV cache lives in HBM as (num_pages, KVH, page_size, D) — page
    major, so one page (all kv heads' slices for page_size tokens) is a
    single contiguous DMA; pages are assigned to sequences through an
    int32 block table;
  * decode query (B, H, D) is viewed as (B, KVH, G, D) with G = H//KVH
    grouped-query heads sharing one KV head;
  * the block table and per-sequence lengths ride scalar prefetch; the
    grid is the flat list of the rows' live steps (steps past
    ceil(len/T) are not in it; its bound is their sum, a dynamic grid
    bound), and scalar prefetch names each step's row, step and first
    table slot;
  * a step GATHERS up to `fold` pages of one sequence and COMPUTES on
    them as one token tile of T = fold * page_size tokens a kv head,
    whatever the page size (`_fold_pages`: 512 tokens or two pages a
    step, VMEM allowing) — a page is only the unit of the gather. The
    pool stays in HBM (`pl.ANY`); the kernel starts the copies of the
    NEXT step's pages into the other half of a two-tile VMEM buffer
    before it waits on its own (`_decode_kernel`), and copies only the
    pages that hold a key the row's query sees (`decode_page_span`):
    the dead slots of a row's last tile, and a window layer's slots
    before its first visible key, are never read;
  * online softmax over token tiles with (KVH, G, 128) lane-broadcast
    running stats and the kv heads batched in one dot_general;
  * positions >= seq_len (and, in a window layer, before the first
    visible key) inside a tile are masked in-block.

What bounds it (v5e; `_decode_kernel` has the history): with page-sized
compute tiles the kernel was bound by what it did with a page once in
VMEM (82 GB/s at page 16); on token tiles it streamed the live KV
at 420-560 GB/s at page 16 and 740 GB/s at page 128, and what was left
at page 16 was the pipeline's work per gathered page, one BlockSpec a
page. Copies the kernel starts itself take that work off the page;
`_gather_plan` says where Mosaic lets it (a pool whose minor dimension
is whole 128-lane tiles) and where the pipeline still fetches. MXU
utilisation is irrelevant at decode G sizes.

Quantized KV pages (ISSUE 6): the cache may instead hold int8 values
with fp32 scales at PER-(slot, kv-head) granularity, stored page-major
in (num_pages, KVH, page_size) arrays addressed by the SAME page ids as
the values — so `BlockAllocator`/`RadixCache`/CoW-fork/truncate stay
byte-level and dtype-agnostic (a page id names a value page AND its
scale rows). Per-slot scales are the only granularity compatible with
quantize-ON-WRITE: a true per-page scale would need to re-quantize the
page's earlier tokens whenever a later token raised the absmax. Writes
quantize (absmax over D per token per head, symmetric, qmax 127);
the decode kernel and the gathered-prefix read paths dequantize in
fp32 before the softmax math, so accuracy loss is bounded by the
round-to-nearest step scale/2 (<= absmax/254 per element; the
quantize->dequantize bound test pins it). Capacity: a page costs
2*KVH*page*(D*width + 4) bytes (K+V + scales), so int8 halves the
payload exactly and the page count at fixed pool bytes grows by
2D/(D+4) (1.94x at D=128) — `paged_page_bytes` is the single source
for that math (engine, bench_ops and the capacity test all use it).
"""
from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_mode

__all__ = ["paged_attention_decode", "paged_attention_decode_tp",
           "decode_page_span", "paged_decode_page_counts",
           "paged_cache_write",
           "paged_cache_write_range", "paged_cache_write_span",
           "alloc_paged_cache", "check_supported_paged", "paged_blockspecs",
           "quantize_kv", "paged_page_bytes", "KV_SCALE_DTYPE"]

NEG_INF = np.float32(-1e30)
_STATS_LANES = 128
_I0 = np.int32(0)
# int8 KV quantization constants: symmetric, qmax 127 (same convention
# as nn.quant.weight_quantize so the rel-err budgets compose), scales
# kept fp32 — the scale multiply happens in the kernel's fp32 softmax
# math anyway, and a bf16 scale would add ~0.4% relative error on top
# of the ~0.8% round-to-nearest step for a 2-bytes/slot-head saving.
KV_QMAX = np.float32(127.0)
KV_SCALE_DTYPE = jnp.float32


def quantize_kv(x):
    """Per-(token, head) symmetric int8 quantization over the head dim.

    x (..., D) float -> (int8 values (..., D), fp32 scales (...,)).
    dequant(q, s) = q * s reproduces x within scale/2 per element
    (absmax/254 — the bound tests/test_serving_quant_kv.py pins)."""
    xf = x.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-10)
    scale = absmax / KV_QMAX
    q = jnp.clip(jnp.round(xf / scale[..., None]), -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), scale.astype(KV_SCALE_DTYPE)


def paged_page_bytes(num_kv_heads, page_size, head_dim, kv_dtype=None):
    """HBM bytes one page costs: K + V payload (+ per-slot fp32 scales
    for int8). The single source for the capacity math quoted in
    SERVING.md — the engine's kv_pool_bytes sizing, bench_ops'
    bytes/token rows and the doubling test all call this."""
    if kv_dtype in (None, "bf16", "bfloat16", "float16"):
        width, scale_b = 2, 0
    elif kv_dtype in ("float32", "fp32"):
        width, scale_b = 4, 0
    elif kv_dtype == "int8":
        width, scale_b = 1, 4
    else:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    return 2 * num_kv_heads * page_size * (head_dim * width + scale_b)


def decode_page_span(seq_lens, page_size, window=None, xp=jnp):
    """The pages of a row's table that hold a key its query sees:
    [first, end), end = ceil(len / page_size), first = 0 or, in a layer
    that attends to the last `window` positions, the page of key len -
    window. The ONE rule the kernel copies by and the engine counts by
    (`paged_decode_page_counts`); `xp` is jnp (the kernel's int32
    scalars: lax division, which Mosaic lowers under x64 where `//`
    does not) or np (the engine's host arithmetic)."""
    if xp is np:
        div = operator.floordiv
    else:
        def div(a, d):
            return jax.lax.div(a, np.int32(d))
    end = div(seq_lens + np.int32(page_size - 1), page_size)
    if window is None:
        return end * 0, end
    return div(xp.maximum(seq_lens - np.int32(window), 0), page_size), end


def _tile_pages(sl, step, page_size, fold, window):
    """[lo, hi): the pages of a row's tile `step` (of `fold` pages) that
    `decode_page_span` names, as int32 scalars in 0 .. fold."""
    first, end = decode_page_span(sl, page_size, window)
    base = step * np.int32(fold)
    return (jnp.clip(first - base, _I0, np.int32(fold)),
            jnp.clip(end - base, _I0, np.int32(fold)))


def _gather_plan(page_size, head_dim, cache_dtype, quantized):
    """How the kernel gathers each kind of page, from the shapes alone:
    (values copied, joined, scales copied).

    Mosaic starts a copy of ONE page out of a pool only where the pool's
    minor dimension is whole 128-lane tiles: value pages where the head
    dim is (D 128, 256), fp32 scale pages where the page is (page 128).
    A kind it refuses is fetched by the pipeline, one BlockSpec a page,
    each page's index clamped to the step's live pages. Copied value
    pages are `joined`: each lands at its offset along the token axis of
    a (KVH, T, D) buffer, which is then the tile as it stands, where a
    page is whole sublane tiles of its type (16 tokens bfloat16, 8
    float32, 32 int8); otherwise a page lands whole in a (fold, KVH,
    page_size, D) buffer and the upcast pages are joined."""
    values = head_dim % 128 == 0
    rows = 32 // jnp.dtype(cache_dtype).itemsize
    return (values, values and page_size % rows == 0,
            quantized and page_size % 128 == 0)


def _decode_kernel(slots_ref, first_ref, sl_ref, row_ref, step_ref, q_ref,
                   *refs, sm_scale, page_size, fold, plan, quantized=False,
                   window=None):
    """One step of the flat grid: `row_ref[w]`'s step `step_ref[w]`. It
    GATHERS its tile's live pages and COMPUTES on them as one token tile
    of T = fold * page_size tokens a kv head: one batched (G, D) x
    (D, T) dot, one mask / max / exp / sum over lane-dense (KVH, G, T)
    scores, one update of m / l / acc and one batched (G, T) x (T, D)
    dot, in float32.

    The gather (`_gather_plan`). The pool stays in HBM, and the step
    copies its pages itself into one half of a two-tile VMEM buffer,
    a page (all kv heads) at its offset along the token axis: step w
    starts step w + 1's copies (of its row or the next: the flat grid
    runs in order) into the other half before it waits on its own, so
    the next tile streams in while this one computes. It copies only
    the pages of `decode_page_span` (`_tile_pages`): none past the
    row's length, none before a window's first visible key; the wait
    runs over the same pages. Buffer rows that no copy of this step
    wrote hold an earlier tile's pages, or the zeros step 0 wrote into
    the V buffers: their scores are masked, and a finite V row times a
    zero weight adds nothing (an unwritten NaN would).

    What bounds it. Before the kernel gathered its own pages a page was
    one BlockSpec of the pipeline, which pays an index map, a compare
    and a copy's start and wait a block: 62 ns a 32 KB page at page 16
    (v5e), where moving it takes 40; and a row's last tile fetched its
    dead slots (the pad page) and masked them. Own copies cost the
    latent kernel (`mla_attention.py`) about 35 ns a 20 KB page.

    History. Round 4 (v5e, B16 KVH8 D128 S2048): one 16-token page a
    step ran at 78 GB/s; folding the FETCHES to 128 tokens a step gave
    96 at page 16 and 401-472 at page 128 — the fold fixed the copies
    and left the compute on page-sized tiles, 64 per-(page, head)
    softmax updates a step on (4, 16) scores, 6 % of a vreg each.
    PR 28 (v5e, the serving cell's call: B64 H32 KVH8 D128 page 16,
    64-page tables, f32 q). Ragged 100-1,000: that kernel 1.93 ms
    (82 GB/s); the same fetches with the body touching one vreg a page
    0.38 ms; the same body over one resident page 1.65 ms — bound by
    what it did with a page in VMEM, not by the copies. Joined tiles
    over the same (B, table / fold) grid: 0.51 / 0.44 / 0.43 ms at
    T 128 / 256 / 512 with the heads batched in one dot_general (0.72 /
    0.74 / 0.59 with a per-head loop). What was left went with B x
    table width, not with the tokens (a KVH 2 shard, a quarter of the
    bytes, took 0.38 ms): every slot of every step costs the scalar
    core its index map, compare and copy issue, live or dead. At the
    cell's contexts (mean 434 of 1,024): 1.52 ms before, 0.42 on that
    grid, 0.31 on the flat grid of live steps, 0.27 (420 GB/s) with the
    table flattened so that a slot's page is one scalar load away; full
    2,048-token rows 1.38 -> 0.24 ms at page 16 (558 GB/s) and 0.295 ->
    0.18 at page 128 (736 GB/s).

    quantized=True gathers the int8 value pages and their fp32 per-slot
    scale pages (same slot ids) and dequantizes each page in fp32 before
    the join — K/V bytes moved drop ~2x.

    window=w (a layer that attends to the last w positions): the row's
    steps begin at the tile that holds its first visible key, len - w
    (`_live_steps`; `step_ref` stays the tile's index in the row's
    table), the running stats are reset there, and the keys before it
    inside that tile are masked."""
    values_copied, joined, scales_copied = plan
    refs = list(refs)

    def take(n):
        out = refs[:n]
        del refs[:n]
        return out

    n_val = 1 if values_copied else fold
    n_scale = (1 if scales_copied else fold) if quantized else 0
    k_in, v_in, ks_in, vs_in = take(n_val), take(n_val), take(n_scale), \
        take(n_scale)
    o_ref, = take(1)
    k_buf, v_buf = take(2) if values_copied else (None, None)
    ks_buf, vs_buf = take(2) if scales_copied else (None, None)
    sem_ref, = take(1) if values_copied or scales_copied else (None,)
    acc_ref, m_ref, l_ref = refs
    sm_scale = np.float32(sm_scale)
    w = pl.program_id(0)
    b = row_ref[w]
    i = step_ref[w]
    sl = sl_ref[b]
    tile_tokens = fold * page_size
    half = jax.lax.rem(w, np.int32(2))
    copied = []                        # (pool, buffer, joined)
    if values_copied:
        copied += [(k_in[0], k_buf, joined), (v_in[0], v_buf, joined)]
    if scales_copied:
        copied += [(ks_in[0], ks_buf, False), (vs_in[0], vs_buf, False)]

    def each_page(step, into, act):
        """act(copy) for every copy of flat step `step`'s live pages into
        half `into` of the buffers."""
        lo, hi = _tile_pages(sl_ref[row_ref[step]], step_ref[step],
                             page_size, fold, window)
        slot0 = first_ref[step]

        @pl.loop(lo, hi)
        def _(f):
            slot = slots_ref[slot0 + f]
            for pool, buf, along_tokens in copied:
                if along_tokens:
                    rows = pl.ds(pl.multiple_of(f * np.int32(page_size),
                                                page_size), page_size)
                    src, dst = pool.at[slot], buf.at[into, :, rows]
                else:
                    src, dst = pool.at[pl.ds(slot, 1)], buf.at[into, f]
                act(pltpu.make_async_copy(src, dst, sem_ref.at[into]))

    if copied:
        @pl.when(w == 0)
        def _first():
            for buf in (v_buf, vs_buf):
                if buf is not None:
                    buf[...] = jnp.zeros_like(buf)
            each_page(w, half, lambda c: c.start())

        # the steps run in order, so the next step's pages (of this row
        # or the next) stream in while this one computes
        @pl.when(w + 1 < pl.num_programs(0))
        def _ahead():
            each_page(w + 1, 1 - half, lambda c: c.start())

        each_page(w, half, lambda c: c.wait())

    def tile(pages_in, buf, scales_in, scale_buf):
        """The step's pages as one (KVH, T, D) fp32 tile."""
        if joined and not quantized:
            return buf[half].astype(jnp.float32)
        pages = []
        for f in range(fold):
            if not values_copied:
                page = pages_in[f][0]
            elif joined:
                page = buf[half, :, f * page_size:(f + 1) * page_size]
            else:
                page = buf[half, f, 0]
            page = page.astype(jnp.float32)             # (KVH, page, D)
            if quantized:                               # fp32 dequant
                scale = (scale_buf[half, f, 0] if scales_copied
                         else scales_in[f][0])
                page = page * scale[:, :, None]
            pages.append(page)
        return pages[0] if fold == 1 else jnp.concatenate(pages, axis=1)

    if window is None:
        first_key, first_step = None, 0
    else:
        first_key = jnp.maximum(sl - np.int32(window), 0)
        first_step = jax.lax.div(first_key, np.int32(tile_tokens))

    @pl.when(i == first_step)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                # (KVH, G, D)
    s = jax.lax.dot_general(q, tile(k_in, k_buf, ks_in, ks_buf),
                            (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                # (KVH, G, T)
    pos = (i * tile_tokens
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2))
    seen = pos < sl
    if window is not None:
        seen = jnp.logical_and(seen, pos >= first_key)
    s = jnp.where(seen, s, NEG_INF)
    m_prev = m_ref[:, :, :1]
    l_prev = l_ref[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                      jnp.exp(m_prev - m_new))
    l_ref[...] = jnp.broadcast_to(
        l_prev * alpha + jnp.sum(p, axis=2, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, tile(v_in, v_buf, vs_in, vs_buf), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when((i + 1) * tile_tokens >= sl)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, :1], np.float32(1e-30))
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def check_supported_paged(q_shape, cache_shape, dtype, kv_dtype=None):
    """Static shape validation mirroring what Mosaic will accept — raise
    here (with a clear message) instead of deep inside lowering. Same
    role as flash_attention.check_supported; the legality test suite
    (tests/test_paged_blockspec_legality.py) sweeps this + the exact
    BlockSpecs below, because interpret=True on CPU hides all Mosaic
    tiling violations (round-1 lesson).

    `dtype` is the QUERY/compute dtype (always bf16/f32); `kv_dtype`
    optionally names a quantized cache storage ("int8" — per-slot-scale
    pages, legal because the value-page block spans the full page/head
    dims and int8's (32, 128) min tile only binds strict sub-blocks)."""
    B, H, D = q_shape
    num_pages, KVH, page_size, Dc = cache_shape
    if D != Dc:
        raise ValueError(f"q head_dim {D} != cache head_dim {Dc}")
    if H % KVH != 0:
        raise ValueError(f"H={H} not a multiple of KVH={KVH}")
    if D % 64 != 0 or D > 256:
        raise ValueError(f"head_dim {D} unsupported (need multiple of 64, "
                         "<= 256)")
    if page_size % 8 != 0:
        raise ValueError(f"page_size {page_size} must be a multiple of 8 "
                         "(sublane tiling)")
    if str(dtype) not in ("bfloat16", "float32"):
        # float16 is deliberately rejected: bf16/f32 are the TPU's native
        # compute dtypes; Mosaic fp16 support is not something we can
        # rely on unvalidated (ADVICE r3 asked to confirm on-chip — still
        # pending; loosen only after a real-chip run passes)
        raise ValueError(f"unsupported dtype {dtype} (TPU-native kernels "
                         "accept bfloat16/float32)")
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} (None for "
                         "the compute dtype, or 'int8' per-slot-scale "
                         "pages)")


# A grid step's token tile: 512 tokens, or two pages where a page is
# larger — VMEM allowing: a tile token costs its K and V pages in both
# halves of the copy buffers plus both fp32 upcasts. With the kernel's
# own copies a row's last tile copies no dead slot, so a wider tile costs
# only its compute; at the serving cells' calls (v5e, the kernel alone,
# two draws of lengths each) T 512 against 256: 0.235, 0.245 against
# 0.248, 0.261 ms at B 64 x 64 pages; 0.998, 1.034 against 1.018, 1.053
# in a 4,096 window and 1.592, 1.487 against 1.572, 1.509 over the
# whole 816-page table at B 48 x 16 query heads a KV head.
_TILE_TOKENS = 512
_TILE_VMEM_BYTES = 8 << 20


def _fold_pages(page_size, max_pages, num_kv_heads, head_dim, cache_dtype):
    """Pages gathered per grid step, from the shapes alone: the token
    tile over the page size, clamped to the table width. Single source
    of truth for the kernel AND the static legality enumeration (they
    drifted once — don't re-fork)."""
    width = jnp.dtype(cache_dtype).itemsize
    per_token = num_kv_heads * head_dim * (2 * 2 * width + 2 * 4)
    tokens = min(max(_TILE_TOKENS, 2 * page_size),
                 _TILE_VMEM_BYTES // per_token)
    return max(1, min(max(tokens, 128) // page_size, max_pages))


def paged_blockspecs(B, H, KVH, D, page_size, num_pages, max_pages=None,
                     quantized=False):
    """The exact (block_shape, array_shape) pairs the pallas_call below
    constructs, plus its scratch shapes (the semaphores' included);
    enumerable for the static legality test without running the kernel.
    The q and out blocks are (1, KVH, G, D). A pool the kernel copies
    from itself (`_gather_plan`) is a `pl.ANY` input whose block is the
    whole array, with a two-tile buffer in scratch; a pool the pipeline
    fetches is `fold` page blocks. The pages are int8 quantized (their
    fp32 scale pools (num_pages, KVH, page_size) beside them), bf16
    otherwise: the two dtypes the engine stores."""
    G = H // KVH
    if max_pages is None:
        max_pages = num_pages
    cache_dtype = jnp.int8 if quantized else jnp.bfloat16
    fold = _fold_pages(page_size, max_pages, KVH, D, cache_dtype)
    values_copied, joined, scales_copied = _gather_plan(
        page_size, D, cache_dtype, quantized)
    pool = (num_pages, KVH, page_size, D)
    scale_pool = (num_pages, KVH, page_size)

    def gathered(array, copied):
        return ([(array, array)] if copied
                else [((1,) + array[1:], array)] * fold)

    specs = (
        [((1, KVH, G, D), (B, KVH, G, D))]                # q block
        + gathered(pool, values_copied) * 2               # k, v pages
        + (gathered(scale_pool, scales_copied) * 2 if quantized else [])
        + [((1, KVH, G, D), (B, KVH, G, D))]              # out block
    )
    scratch = []
    if values_copied:
        scratch += [(2, KVH, fold * page_size, D) if joined
                    else (2, fold, 1, KVH, page_size, D)] * 2
    if scales_copied:
        scratch += [(2, fold, 1, KVH, page_size)] * 2
    if values_copied or scales_copied:
        scratch += [(2,)]                                 # DMA semaphores
    scratch += [(KVH, G, D), (KVH, G, _STATS_LANES), (KVH, G, _STATS_LANES)]
    return specs, scratch


def _live_steps(seq_lens, tile_tokens, steps_per_row, fold, window=None):
    """The kernel's grid: the flat list of the rows' LIVE steps. Row b
    runs ceil(len / T) of them (one for an empty row, so that every
    output block is written); the bound is their sum, known on the
    device only. A step past a row's length costs the scalar core as
    much as a live one (_decode_kernel), and at serving lengths half
    the table is such steps.

    With a `window` a row's steps begin at the tile of its first visible
    key, len - window: a row far past the window runs window / T + 1
    steps whatever its length, and `step_of` stays the tile's index in
    the row's table.

    Returns (total, row_of, step_of, first_of): for flat step w its
    row, its step within the row and its first slot in the flattened
    (B * steps_per_row * fold) block table — one scalar load then names
    a page, where (row, step) would take three. Every entry, live or
    not, names slots inside the table, and the caller appends a tail of
    zeros: the pipeline evaluates index maps some steps AHEAD of the
    running one, past the bound too, and a scalar load is not
    bounds-checked."""
    B = seq_lens.shape[0]
    steps_of = jnp.clip((seq_lens + (tile_tokens - 1)) // tile_tokens,
                        1, steps_per_row)
    if window is not None:
        skipped = jnp.minimum(
            jnp.maximum(seq_lens - window, 0) // tile_tokens, steps_of - 1)
        steps_of = steps_of - skipped
    ends = jnp.cumsum(steps_of, dtype=jnp.int32)
    flat = jnp.arange(B * steps_per_row + 1, dtype=jnp.int32)
    row_of = jnp.minimum(
        jnp.sum(flat[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        B - 1)
    step_of = flat - (ends - steps_of)[row_of]
    if window is not None:
        step_of = step_of + skipped[row_of]
    step_of = jnp.minimum(step_of, steps_per_row - 1)
    first_of = (row_of * steps_per_row + step_of) * fold
    return ends[-1], row_of, step_of, first_of


def paged_decode_page_counts(seq_lens, page_size, max_pages, num_kv_heads,
                             head_dim, cache_dtype, window=None):
    """(tile pages, copied pages) of one `paged_attention_decode` call
    over a (B, max_pages) table, summed over its rows, by the kernel's
    own rules and in host arithmetic (NumPy, no device work): the page
    slots of the flat grid's live steps (`fold` a step, `_live_steps`:
    one step for an empty row, a window layer's steps from its first
    visible key's tile) and the pages the kernel copies
    (`decode_page_span`). Their ratio is the share of a gathered tile
    that is live."""
    fold = _fold_pages(page_size, max_pages, num_kv_heads, head_dim,
                       cache_dtype)
    steps_per_row = -(-max_pages // fold)
    sl = np.minimum(np.asarray(seq_lens, np.int64), max_pages * page_size)
    first, end = decode_page_span(sl, page_size, window, xp=np)
    steps = np.clip(-(-end // fold), 1, steps_per_row)
    steps = steps - np.minimum(first // fold, steps - 1)
    return int(steps.sum()) * fold, int((end - first).sum())


def paged_attention_decode(q, k_cache, v_cache, block_tables, seq_lens,
                           sm_scale=None, k_scale=None, v_scale=None,
                           window=None):
    """One decode step of attention over a paged KV cache.

    q:            (B, H, D) — current-step queries.
    k/v_cache:    (num_pages, KVH, page_size, D).
    block_tables: (B, max_pages) int32 — page ids per sequence, position
                  j holds the page covering tokens [j*page_size,
                  (j+1)*page_size); unused slots must hold a valid page
                  id (0 is fine — masked out by seq_lens).
    seq_lens:     (B,) int32 — live tokens per sequence (including the
                  token being decoded).
    k/v_scale:    optional (num_pages, KVH, page_size) fp32 — per-slot
                  dequant scales for int8 caches (both or neither);
                  the kernel streams the scale pages alongside the
                  value pages and dequantizes in fp32.
    window:       optional int (static) — the layer attends to the last
                  `window` positions only: the query (at seq_len - 1)
                  sees key j iff seq_len - window <= j < seq_len. Table
                  slots before the first visible key's page are never
                  read and may hold the pad page. None lowers as ever.
    Returns (B, H, D).
    """
    B, H, D = q.shape
    quantized = k_scale is not None or v_scale is not None
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if quantized and str(k_cache.dtype) != "int8":
        raise ValueError(f"scales given but cache dtype is "
                         f"{k_cache.dtype}, not int8")
    if not quantized and str(k_cache.dtype) == "int8":
        raise ValueError("int8 cache needs k_scale/v_scale")
    check_supported_paged(q.shape, k_cache.shape, q.dtype,
                          kv_dtype="int8" if quantized else None)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    return _paged_decode_once(q, k_cache, v_cache, block_tables, seq_lens,
                              k_scale, v_scale, sm_scale=float(sm_scale),
                              window=window, interpret=_interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "window", "interpret"))
def _paged_decode_once(q, k_cache, v_cache, block_tables, seq_lens, k_scale,
                       v_scale, *, sm_scale, window, interpret):
    """`paged_attention_decode`'s call, traced and lowered ONCE a program
    however many layers make it (as `mla_attention._mla_decode_once` and
    `flash_attention._fwd_positions_once` are): the kernel's body starts
    and waits on its copies itself and lowers longer than a BlockSpec
    pipeline's."""
    B, H, D = q.shape
    num_pages, KVH, page_size, _ = k_cache.shape
    max_pages = block_tables.shape[1]
    quantized = k_scale is not None
    G = H // KVH
    qg = q.reshape(B, KVH, G, D)
    bt = block_tables.astype(jnp.int32)
    # a length past the table reads the whole table, and no further
    sl = jnp.minimum(seq_lens.astype(jnp.int32), max_pages * page_size)

    # A step gathers up to `fold` pages and computes on them as one token
    # tile (_decode_kernel has the measurements). Pad the block table
    # to a fold multiple; a padded slot lies past every row's length and
    # is never copied.
    fold = _fold_pages(page_size, max_pages, KVH, D, k_cache.dtype)
    if max_pages % fold != 0:
        pad = fold - max_pages % fold
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
        max_pages += pad

    total, row_of, step_of, first_of = _live_steps(
        sl, fold * page_size, max_pages // fold, fold, window=window)
    # Every scalar-prefetch array ends in 128+ zero words (row 0, step 0,
    # slot 0, the pad page, length 0: all valid) on a 128-word boundary.
    # Without a tail a v5e HALTED (on-device check) on the engine's small
    # buckets — B 2 x 4-, 8-, 16-page tables, off and on with the data —
    # because the index maps are evaluated past the last step and read
    # whatever follows the arrays in SMEM as a row and a page id; 8 more
    # valid entries on the three step arrays were enough in a probe
    # (PR 28), interpret mode clamps the read and shows nothing.
    prefetch = [jnp.pad(a, (0, -a.shape[0] % 128 + 128))
                for a in (bt.reshape(-1), first_of, sl, row_of, step_of)]

    plan = _gather_plan(page_size, D, k_cache.dtype, quantized)
    values_copied, joined, scales_copied = plan
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               page_size=page_size, fold=fold, plan=plan,
                               quantized=quantized, window=window)

    def row_block(w, slots, first_of, sl, row_of, step_of):
        return row_of[w], _I0, _I0, _I0

    def gathered(pool, copied):
        """(in_specs, args, scratch) of one pool: copied by the kernel,
        or fetched a page a BlockSpec with each page's index clamped to
        the step's live pages (a dead slot re-reads a live page)."""
        if copied:
            return [pl.BlockSpec(memory_space=pl.ANY)], [pool], [
                pltpu.VMEM((2, KVH, fold * page_size, D) if joined
                           and pool.ndim == 4 else
                           (2, fold, 1) + pool.shape[1:], pool.dtype)]

        def page_spec(f):
            def index(w, slots, first_of, sl, row_of, step_of):
                lo, hi = _tile_pages(sl[row_of[w]], step_of[w], page_size,
                                     fold, window)
                g = jnp.maximum(jnp.minimum(np.int32(f), hi - 1), lo)
                return (slots[first_of[w] + g],) + (_I0,) * (pool.ndim - 1)
            return pl.BlockSpec((1,) + pool.shape[1:], index)
        return [page_spec(f) for f in range(fold)], [pool] * fold, []

    in_specs, args, scratch = [pl.BlockSpec((1, KVH, G, D), row_block)], \
        [qg], []
    pools = [(k_cache, values_copied), (v_cache, values_copied)]
    if quantized:
        pools += [(k_scale, scales_copied), (v_scale, scales_copied)]
    for pool, copied in pools:
        specs, pool_args, pool_scratch = gathered(pool, copied)
        in_specs += specs
        args += pool_args
        scratch += pool_scratch
    if scratch:
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(total,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVH, G, D), row_block),
        scratch_shapes=scratch + [
            pltpu.VMEM((KVH, G, D), jnp.float32),
            pltpu.VMEM((KVH, G, _STATS_LANES), jnp.float32),
            pltpu.VMEM((KVH, G, _STATS_LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_decode",
    )(*prefetch, *args)
    return out.reshape(B, H, D)


def paged_attention_decode_tp(q, k_cache, v_cache, block_tables, seq_lens,
                              mesh, axis="model", sm_scale=None,
                              k_scale=None, v_scale=None):
    """Tensor-parallel decode attention: query heads and the KV pages'
    head dim sharded over mesh axis `axis` (ISSUE 8).

    Sharding layout — page IDS are global (the host-side
    BlockAllocator/RadixCache never see the mesh), page CONTENTS are
    head-sharded: q (B, H, D) splits H, the caches
    (num_pages, KVH, page, D) and int8 scale pages (num_pages, KVH,
    page) split KVH, block_tables/seq_lens are replicated. Each shard
    attends its own KVH/tp kv heads against its own H/tp query heads
    (G = H/KVH is shard-invariant), so NO collective is needed here —
    the psum lives in the row-parallel o_proj that consumes the output.

    One lowering on every backend: a shard_map manual over EVERY mesh
    axis, each shard running the plain kernel on its local head slice
    (so the kernel's GB/s applies per chip unchanged). Mosaic refuses
    anything less — a kernel under GSPMD constraints or under a
    partial-manual map "cannot be automatically partitioned" — and the
    CPU backend runs the same map over the interpret-mode kernel
    bit-exactly. The specs name only `axis`; the other axes see
    replicated operands. Returns (B, H, D) sharded on H over `axis`.
    """
    from jax.sharding import PartitionSpec as P
    B, H, D = q.shape
    KVH = k_cache.shape[1]
    tp = int(mesh.shape[axis])
    if H % tp:
        raise ValueError(f"H={H} not divisible by tp={tp}")
    if KVH % tp:
        raise ValueError(f"KVH={KVH} not divisible by tp={tp}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)

    q_spec = P(None, axis, None)
    page_spec = P(None, axis, None, None)
    scale_spec = P(None, axis, None)

    def local(qq, kc, vc, bt, sl, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention_decode(
            qq, kc, vc, bt, sl, sm_scale=sm_scale, k_scale=ks, v_scale=vs)

    in_specs = (q_spec, page_spec, page_spec, P(), P())
    args = (q, k_cache, v_cache, block_tables, seq_lens)
    if k_scale is not None:
        in_specs = in_specs + (scale_spec, scale_spec)
        args = args + (k_scale, v_scale)
    f = shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=q_spec,
                  check_vma=False)
    return f(*args)


_SCALE_DNUMS = jax.lax.ScatterDimensionNumbers(
    update_window_dims=(),
    inserted_window_dims=(0, 1, 2),
    scatter_dims_to_operand_dims=(0, 1, 2))


def _scatter_scales(scale_buf, idx, scales):
    """Scatter per-(token, head) fp32 scales into the page-major scale
    array using the SAME (page, head, slot) indices as the value
    scatter — dead positions collide on page 0 exactly like the value
    writes (pad-page scale rows are never read un-masked)."""
    return jax.lax.scatter(
        scale_buf, idx, scales.reshape(-1).astype(scale_buf.dtype),
        _SCALE_DNUMS, indices_are_sorted=False, unique_indices=False)


def _maybe_quantize(k_cache, k_new, k_scale):
    """Route a write through quantize-on-write when the cache is int8.
    Returns (values to scatter, per-slot scales or None). Raises on a
    scale/dtype mismatch so a mis-threaded engine config fails loudly
    at trace time, not as silent garbage KV."""
    if k_scale is None:
        if str(k_cache.dtype) == "int8":
            raise ValueError("int8 cache write needs scale buffers")
        return k_new, None
    if str(k_cache.dtype) != "int8":
        raise ValueError(f"scale buffer given but cache dtype is "
                         f"{k_cache.dtype}, not int8")
    return quantize_kv(k_new)


def paged_cache_write_range(k_cache, v_cache, k_new, v_new, block_table,
                            length, start=0, k_scale=None, v_scale=None):
    """Scatter a prefill span's K/V (one sequence) into the paged cache.

    k_new/v_new:  (S, KVH, D) — keys/values for token positions
                  start..start+S-1 (S may exceed `length`: the tail is
                  prompt padding).
    block_table:  (max_pages,) int32 — the sequence's page ids; slot j
                  covers tokens [j*page_size, (j+1)*page_size).
    length:       () int32 — live tokens IN THIS SPAN; span positions
                  >= length are routed to page 0, the reserved pad page
                  the decode kernel never reads un-masked (same contract
                  as the padded block-table slots in
                  `paged_attention_decode`).
    start:        () int32 — absolute token position of k_new[0]
                  (chunked prefill writes a partial prompt at an
                  offset; whole-prompt callers keep the default 0).
    k/v_scale:    optional (num_pages, KVH, page_size) fp32 scale
                  arrays (int8 caches): the span is quantized on write
                  and its per-slot scales land at the same
                  (page, head, slot) addresses.
    Returns the updated (k_cache, v_cache) — plus (k_scale, v_scale)
    when scale buffers were passed.

    Serving prefill companion of `paged_cache_write`: one scatter moves
    a whole chunk instead of a token per step, so the engine's prefill
    program is a single fused write (the read path stays the Pallas
    kernel).
    """
    num_pages, KVH, page_size, D = k_cache.shape
    S = k_new.shape[0]
    k_new, k_sc = _maybe_quantize(k_cache, k_new, k_scale)
    v_new, v_sc = _maybe_quantize(v_cache, v_new, v_scale)
    t = jnp.arange(S, dtype=jnp.int32)
    live = t < jnp.asarray(length, jnp.int32)
    pos = t + jnp.asarray(start, jnp.int32)
    page_idx = jax.lax.div(pos, jnp.int32(page_size))
    page_off = jax.lax.rem(pos, jnp.int32(page_size))
    pages = jnp.where(live, block_table.astype(jnp.int32)[page_idx], 0)
    heads = jnp.arange(KVH, dtype=jnp.int32)
    idx = jnp.stack([
        jnp.broadcast_to(pages[:, None], (S, KVH)),
        jnp.broadcast_to(heads[None, :], (S, KVH)),
        jnp.broadcast_to(page_off[:, None], (S, KVH)),
    ], axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,),
        inserted_window_dims=(0, 1, 2),
        scatter_dims_to_operand_dims=(0, 1, 2))
    # padded positions collide on page 0 — duplicates allowed there (the
    # pad page's contents are never read un-masked)
    k_cache = jax.lax.scatter(
        k_cache, idx.reshape(S * KVH, 3),
        k_new.reshape(S * KVH, D).astype(k_cache.dtype), dnums,
        indices_are_sorted=False, unique_indices=False)
    v_cache = jax.lax.scatter(
        v_cache, idx.reshape(S * KVH, 3),
        v_new.reshape(S * KVH, D).astype(v_cache.dtype), dnums,
        indices_are_sorted=False, unique_indices=False)
    if k_sc is None:
        return k_cache, v_cache
    k_scale = _scatter_scales(k_scale, idx.reshape(S * KVH, 3), k_sc)
    v_scale = _scatter_scales(v_scale, idx.reshape(S * KVH, 3), v_sc)
    return k_cache, v_cache, k_scale, v_scale


def paged_cache_write_span(k_cache, v_cache, k_new, v_new, block_tables,
                           lengths, starts, k_scale=None, v_scale=None):
    """Scatter a BATCH of short spans' K/V into the paged cache — the
    speculative-decoding VERIFY write: every sequence lands its
    [last emitted token, draft_1..draft_K] K/V in one fused scatter.

    k_new/v_new:   (B, S, KVH, D) — row b holds keys/values for token
                   positions starts[b]..starts[b]+S-1 (positions past
                   lengths[b] are bucket padding).
    block_tables:  (B, max_pages) int32 — per-sequence page ids; slot j
                   covers tokens [j*page_size, (j+1)*page_size).
    lengths:       (B,) int32 — live tokens in each row's span (the
                   verify step's 1 + draft_len); span positions >=
                   lengths[b] route to page 0, the reserved pad page
                   (the `paged_attention_decode` padding contract).
    starts:        (B,) int32 — absolute position of k_new[b, 0]
                   (seq_len - 1: the first input token overwrites its
                   own slot idempotently, exactly like the decode-step
                   write — a supervisor retry re-runs bit-identically;
                   quantize-on-write keeps idempotence: the same fp
                   input always quantizes to the same (values, scale)).
    k/v_scale:     optional fp32 scale arrays for int8 caches.
    Returns the updated (k_cache, v_cache) (+ scales when given).

    Batched sibling of `paged_cache_write_range` (single-sequence
    prefill span) and `paged_cache_write` (one token per sequence);
    kept a pure-XLA scatter like both — a verify span moves at most
    (K+1) tokens per sequence, not a bandwidth problem; the read path
    stays the gathered-prefix attention / Pallas kernel.
    """
    num_pages, KVH, page_size, D = k_cache.shape
    B, S = k_new.shape[:2]
    k_new, k_sc = _maybe_quantize(k_cache, k_new, k_scale)
    v_new, v_sc = _maybe_quantize(v_cache, v_new, v_scale)
    P = block_tables.shape[1]
    t = jnp.arange(S, dtype=jnp.int32)[None, :]                   # (1, S)
    live = t < jnp.asarray(lengths, jnp.int32)[:, None]           # (B, S)
    pos = t + jnp.asarray(starts, jnp.int32)[:, None]             # (B, S)
    page_idx = jax.lax.div(pos, jnp.int32(page_size))
    page_off = jax.lax.rem(pos, jnp.int32(page_size))
    # dead positions may carry pos < 0 (padded batch rows start at -1)
    # or past-the-table pages: clamp the gather index — the page id is
    # forced to 0 by `live` anyway, and their offsets fall out of
    # bounds (FILL_OR_DROP discards them)
    safe_idx = jnp.clip(page_idx, 0, P - 1)
    pages = jnp.where(
        live,
        jnp.take_along_axis(block_tables.astype(jnp.int32), safe_idx,
                            axis=1),
        0)
    heads = jnp.arange(KVH, dtype=jnp.int32)
    idx = jnp.stack([
        jnp.broadcast_to(pages[:, :, None], (B, S, KVH)),
        jnp.broadcast_to(heads[None, None, :], (B, S, KVH)),
        jnp.broadcast_to(page_off[:, :, None], (B, S, KVH)),
    ], axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,),
        inserted_window_dims=(0, 1, 2),
        scatter_dims_to_operand_dims=(0, 1, 2))
    # dead positions collide on page 0 — duplicates allowed there (pad
    # page contents are never read un-masked), so uniqueness must NOT
    # be declared (same contract note as paged_cache_write)
    k_cache = jax.lax.scatter(
        k_cache, idx.reshape(B * S * KVH, 3),
        k_new.reshape(B * S * KVH, D).astype(k_cache.dtype), dnums,
        indices_are_sorted=False, unique_indices=False)
    v_cache = jax.lax.scatter(
        v_cache, idx.reshape(B * S * KVH, 3),
        v_new.reshape(B * S * KVH, D).astype(v_cache.dtype), dnums,
        indices_are_sorted=False, unique_indices=False)
    if k_sc is None:
        return k_cache, v_cache
    k_scale = _scatter_scales(k_scale, idx.reshape(B * S * KVH, 3), k_sc)
    v_scale = _scatter_scales(v_scale, idx.reshape(B * S * KVH, 3), v_sc)
    return k_cache, v_cache, k_scale, v_scale


def alloc_paged_cache(num_kv_heads, num_pages, page_size, head_dim,
                      dtype=jnp.bfloat16, kv_dtype=None):
    """Allocate an empty paged KV cache pair in the kernel's layout.

    kv_dtype="int8" returns (k, v, k_scale, v_scale): int8 value pages
    plus fp32 per-slot scale pages addressed by the same page ids
    (all-zero scales dequantize the pad page to exact zeros, matching
    the bf16 pad contract)."""
    shape = (num_pages, num_kv_heads, page_size, head_dim)
    if kv_dtype == "int8":
        sshape = (num_pages, num_kv_heads, page_size)
        return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.zeros(sshape, KV_SCALE_DTYPE),
                jnp.zeros(sshape, KV_SCALE_DTYPE))
    if kv_dtype is not None:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def paged_cache_write(k_cache, v_cache, k_new, v_new, block_tables,
                      write_pos, k_scale=None, v_scale=None):
    """Scatter one step's K/V into the paged cache.

    k_new/v_new: (B, KVH, D) — the current token's key/value per head.
    write_pos:   (B,) int32 — token index being written (seq_len - 1).
    k/v_scale:   optional fp32 scale arrays for int8 caches
                 (quantize-on-write, same contract as the span writes).
    Returns the updated (k_cache, v_cache) (+ scales when given).

    The scatter is a pure-XLA dynamic update (one token per sequence per
    step — not a bandwidth problem); the read path is the Pallas kernel.
    """
    num_pages, KVH, page_size, D = k_cache.shape
    B = k_new.shape[0]
    k_new, k_sc = _maybe_quantize(k_cache, k_new, k_scale)
    v_new, v_sc = _maybe_quantize(v_cache, v_new, v_scale)
    pos = write_pos.astype(jnp.int32)
    page_idx = jax.lax.div(pos, jnp.int32(page_size))
    page_off = jax.lax.rem(pos, jnp.int32(page_size))
    pages = jnp.take_along_axis(block_tables.astype(jnp.int32),
                                page_idx[:, None], axis=1)[:, 0]   # (B,)
    heads = jnp.arange(KVH, dtype=jnp.int32)
    # scatter indices (B, KVH, 3) over cache dims (page, head, slot)
    idx = jnp.stack([
        jnp.broadcast_to(pages[:, None], (B, KVH)),
        jnp.broadcast_to(heads[None, :], (B, KVH)),
        jnp.broadcast_to(page_off[:, None], (B, KVH)),
    ], axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,),
        inserted_window_dims=(0, 1, 2),
        scatter_dims_to_operand_dims=(0, 1, 2))
    # NOT unique: a bucket-padded decode batch (serving engine) carries
    # pad rows with write_pos = -1 that all fold to the same (page 0,
    # head, -1) index — FILL_OR_DROP discards them (offset out of
    # bounds), but declaring uniqueness over duplicate indices is
    # undefined behavior, so don't
    k_cache = jax.lax.scatter(
        k_cache, idx.reshape(B * KVH, 3),
        k_new.reshape(B * KVH, D).astype(k_cache.dtype), dnums,
        indices_are_sorted=False, unique_indices=False)
    v_cache = jax.lax.scatter(
        v_cache, idx.reshape(B * KVH, 3),
        v_new.reshape(B * KVH, D).astype(v_cache.dtype), dnums,
        indices_are_sorted=False, unique_indices=False)
    if k_sc is None:
        return k_cache, v_cache
    k_scale = _scatter_scales(k_scale, idx.reshape(B * KVH, 3), k_sc)
    v_scale = _scatter_scales(v_scale, idx.reshape(B * KVH, 3), v_sc)
    return k_cache, v_cache, k_scale, v_scale
