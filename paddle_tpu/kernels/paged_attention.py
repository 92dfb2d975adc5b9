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
  * grid (B, max_pages) with the page dimension innermost: the block
    table and per-sequence lengths ride scalar prefetch, the page index
    map gathers `block_tables[b, i]` so Pallas streams exactly the
    pages this sequence owns (double-buffered HBM->VMEM), one whole
    page (all kv heads) per step;
  * online softmax over pages with (G, 128) lane-broadcast running
    stats; pages past ceil(len/page_size) skip all compute via pl.when;
  * positions >= seq_len inside the last page are masked in-block.

The kernel is bandwidth-bound (one pass over the live KV), which is the
same regime the reference's CUDA kernel targets; MXU utilisation is
irrelevant at decode G sizes.

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

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_mode

__all__ = ["paged_attention_decode", "paged_attention_decode_tp",
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


def _decode_kernel(bt_ref, sl_ref, q_ref, *rest_refs, sm_scale, page_size,
                   nsteps, kvh, fold, quantized=False):
    """Grid (B, nsteps); one step streams `fold` gathered pages for ALL
    kv heads. Folding matters: with one 16-token page per step the DMAs
    are 64 KB and per-step overhead dominates (measured 78 GB/s on v5e;
    401 GB/s once ~128 tokens move per step), so small serving pages
    are batched until a step carries >= ~128 tokens' worth of KV.

    quantized=True streams int8 value pages plus their fp32 per-slot
    scale pages (same gathered page ids) and dequantizes on the VMEM
    side — K/V bytes moved drop ~2x, which is the whole win in this
    bandwidth-bound regime."""
    k_refs = rest_refs[:fold]
    v_refs = rest_refs[fold:2 * fold]
    if quantized:
        ks_refs = rest_refs[2 * fold:3 * fold]
        vs_refs = rest_refs[3 * fold:4 * fold]
        o_ref, acc_ref, m_ref, l_ref = rest_refs[4 * fold:]
    else:
        o_ref, acc_ref, m_ref, l_ref = rest_refs[2 * fold:]
    sm_scale = np.float32(sm_scale)
    b = pl.program_id(0)
    i = pl.program_id(1)
    sl = sl_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * fold * page_size < sl)
    def _step():
        for f in range(fold):                          # static unroll
            for h in range(kvh):                       # static unroll
                q = q_ref[0, h].astype(jnp.float32)    # (G, D)
                k = k_refs[f][0, h].astype(jnp.float32)  # (page, D)
                v = v_refs[f][0, h].astype(jnp.float32)
                if quantized:
                    k = k * ks_refs[f][0, h][:, None]  # fp32 dequant
                    v = v * vs_refs[f][0, h][:, None]
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                s = s * sm_scale                       # (G, page)
                G, P = s.shape
                pos = ((i * fold + f) * page_size
                       + jax.lax.broadcasted_iota(jnp.int32, (G, P), 1))
                s = jnp.where(pos < sl, s, NEG_INF)
                m_prev = m_ref[h, :, :1]
                l_prev = l_ref[h, :, :1]
                m_cur = jnp.max(s, axis=1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
                alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                                  jnp.exp(m_prev - m_new))
                l_ref[h] = jnp.broadcast_to(
                    l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                    l_ref.shape[1:])
                m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    @pl.when(i == nsteps - 1)
    def _finalize():
        for h in range(kvh):
            l = jnp.maximum(l_ref[h, :, :1], np.float32(1e-30))
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


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


def _fold_pages(page_size, max_pages, fold_tokens=None):
    """Pages batched per grid step: max(128 tokens, 2 pages), clamped to
    the table width. Single source of truth for the kernel AND the
    static legality enumeration (they drifted once — don't re-fork)."""
    if fold_tokens is None:
        fold_tokens = max(128, 2 * page_size)
    return max(1, min(fold_tokens // page_size, max_pages))


def paged_blockspecs(B, H, KVH, D, page_size, num_pages, max_pages=None,
                     fold_tokens=None, quantized=False):
    """The exact (block_shape, array_shape) pairs the pallas_call below
    constructs — including the `fold` repetition of the k/v page specs
    the folded grid uses — plus the VMEM scratch shapes; enumerable for
    the static legality test without running the kernel. quantized=True
    appends the fp32 scale-page specs ((1, KVH, page_size) blocks over
    (num_pages, KVH, page_size) arrays — legal because both trailing
    block dims equal the array dims) the int8 path adds."""
    G = H // KVH
    if max_pages is None:
        max_pages = num_pages
    fold = _fold_pages(page_size, max_pages, fold_tokens)
    page = ((1, KVH, page_size, D), (num_pages, KVH, page_size, D))
    scale = ((1, KVH, page_size), (num_pages, KVH, page_size))
    specs = (
        [((1, KVH, G, D), (B, KVH, G, D))]                # q block
        + [page] * fold                                   # k pages
        + [page] * fold                                   # v pages
        + ([scale] * (2 * fold) if quantized else [])     # k/v scale pages
        + [((1, KVH, G, D), (B, KVH, G, D))]              # out block
    )
    scratch = [(KVH, G, D), (KVH, G, _STATS_LANES), (KVH, G, _STATS_LANES)]
    return specs, scratch


def paged_attention_decode(q, k_cache, v_cache, block_tables, seq_lens,
                           sm_scale=None, fold_tokens=None,
                           k_scale=None, v_scale=None):
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
    Returns (B, H, D).
    """
    B, H, D = q.shape
    num_pages, KVH, page_size, _ = k_cache.shape
    max_pages = block_tables.shape[1]
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
    G = H // KVH
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KVH, G, D)
    bt = block_tables.astype(jnp.int32)
    sl = seq_lens.astype(jnp.int32)

    # Fold pages so one grid step moves >= max(128 tokens, 2 pages) of
    # KV (swept on v5e at B16 KVH8 D128 S2048: 16-token steps ran at
    # 78 GB/s — DMA-latency-bound — vs 96/188/268 GB/s folded at
    # page 16/32/64, and 2-page folds at page 128 hit 472 GB/s vs 401
    # unfolded; folds deeper than this regressed every small-page
    # config). Pad the block table to a fold multiple; padded slots
    # reuse page 0 and are masked by seq_lens.
    fold = _fold_pages(page_size, max_pages, fold_tokens)
    if max_pages % fold != 0:
        pad = fold - max_pages % fold
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
        max_pages += pad
    nsteps = max_pages // fold

    kernel = functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                               page_size=page_size, nsteps=nsteps,
                               kvh=KVH, fold=fold, quantized=quantized)

    def page_spec(f):
        return pl.BlockSpec(
            (1, KVH, page_size, D),
            lambda b, i, bt, sl, f=f: (bt[b, i * fold + f],
                                       _I0, _I0, _I0))

    def scale_spec(f):
        # same gathered page id as the value page it scales
        return pl.BlockSpec(
            (1, KVH, page_size),
            lambda b, i, bt, sl, f=f: (bt[b, i * fold + f], _I0, _I0))

    scale_specs = ([scale_spec(f) for f in range(fold)] * 2
                   if quantized else [])
    scale_args = ([k_scale] * fold + [v_scale] * fold) if quantized else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nsteps),
        in_specs=(
            [pl.BlockSpec((1, KVH, G, D),
                          lambda b, i, *_: (b, _I0, _I0, _I0))]
            + [page_spec(f) for f in range(fold)]      # k pages
            + [page_spec(f) for f in range(fold)]      # v pages
            + scale_specs                              # k/v scale pages
        ),
        out_specs=pl.BlockSpec((1, KVH, G, D),
                               lambda b, i, *_: (b, _I0, _I0, _I0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, G, D), jnp.float32),
            pltpu.VMEM((KVH, G, _STATS_LANES), jnp.float32),
            pltpu.VMEM((KVH, G, _STATS_LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret_mode(),
        name="paged_attention_decode",
    )(bt, sl, qg, *([k_cache] * fold), *([v_cache] * fold), *scale_args)
    return out.reshape(B, H, D)


def paged_attention_decode_tp(q, k_cache, v_cache, block_tables, seq_lens,
                              mesh, axis="model", sm_scale=None,
                              fold_tokens=None, k_scale=None, v_scale=None):
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
            qq, kc, vc, bt, sl, sm_scale=sm_scale,
            fold_tokens=fold_tokens, k_scale=ks, v_scale=vs)

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
