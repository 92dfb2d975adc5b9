"""Attention functionals.

Parity: reference `python/paddle/nn/functional/flash_attention.py`
(flash_attention:242, scaled_dot_product_attention:976, flashmask_attention:1098).

TPU-native: the default path is a jnp composition that XLA fuses well at
moderate sequence lengths; for long sequences `paddle_tpu.kernels.
flash_attention` provides a Pallas fused kernel (used automatically when
available and shapes allow). Layouts follow the reference: (batch, seqlen,
num_heads, head_dim).
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp

from ...framework.random import rng_key
from ...ops.dispatch import apply_op

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "flashmask_attention", "sdp_kernel"]

_USE_PALLAS = [True]

# Every call the Pallas path refused and the XLA composition served
# instead, by (api, q shape, k shape, dtype, reason). Falling through
# is legal (decode's Sq=1, odd test shapes) but must be countable: a
# chip run asserts its shapes took the kernel. Also exposed as the
# "attention_pallas_refusals" provider of profiler.counters().
_refusals: collections.Counter = collections.Counter()


def _refusal_counters() -> dict:
    return {repr(k): n for k, n in _refusals.items()}


def _note_refusal(api, q_shape, k_shape, dtype, err):
    from ... import profiler        # lazy, like ops.dispatch's hook
    profiler.register_counter_provider("attention_pallas_refusals",
                                       _refusal_counters)   # idempotent
    _refusals[(api, tuple(q_shape), tuple(k_shape), str(dtype),
               str(err))] += 1


def pallas_refusals(reset=False) -> dict:
    """{(api, q_shape, k_shape, dtype, reason): calls} served by the XLA
    composition because the Pallas kernel refused the shape."""
    out = dict(_refusals)
    if reset:
        _refusals.clear()
    return out


def _sdpa_ref(q, k, v, mask=None, dropout_p=0.0, causal=False, key=None,
              training=True, scale=None):
    """(B, S, H, D) attention, fp32 softmax accumulation."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qt = jnp.swapaxes(q, 1, 2)  # B,H,S,D
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    scores = scores.astype(jnp.float32)
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(cm, scores, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -jnp.inf)
        else:
            scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # B,S,H,D


def _per_shard(fn, q_shape, k_shape):
    """`fn(q, k, v)` over (B, S, H, D) under the ambient hybrid mesh.
    GSPMD cannot partition a Mosaic kernel ("cannot be automatically
    partitioned"), so with a multi-device mesh live the kernel runs
    inside a shard_map manual over EVERY axis: batch split over 'data',
    heads over 'model' (wherever the axis divides them; replicated
    otherwise). Each shard attends its own (batch, head) slice, which
    needs no collective. A mesh that splits the sequence ('sep') needs
    the ring kernel, and inside the pipeline's partial-manual map no
    second map can open: both raise ValueError (-> XLA composition)."""
    from ...distributed.fleet.mpu import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return fn
    sizes = dict(mesh.shape)
    for axis in ("sep", "pipe"):
        if sizes.get(axis, 1) > 1:
            raise ValueError(f"flash kernel cannot run per shard of a "
                             f"{axis!r}-split mesh {sizes}")
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    batch = "data" if sizes.get("data", 1) > 1 and \
        q_shape[0] % sizes["data"] == 0 else None
    heads = "model" if sizes.get("model", 1) > 1 and \
        q_shape[2] % sizes["model"] == 0 and \
        k_shape[2] % sizes["model"] == 0 else None
    spec = P(batch, None, heads, None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Parity: nn/functional/flash_attention.py:976. Shapes (B, S, H, D)."""
    can_pallas = (_USE_PALLAS[0] and attn_mask is None and dropout_p == 0.0)
    if can_pallas:
        try:
            from ...kernels import flash_attention as pallas_fa
            pallas_fa.check_supported(
                tuple(query.shape), tuple(key.shape), query.dtype)
            def _f(q, k, v):
                return pallas_fa.flash_attention_bshd(q, k, v, causal=is_causal)
            return apply_op(
                "flash_attention",
                _per_shard(_f, tuple(query.shape), tuple(key.shape)),
                query, key, value)
        except ValueError as e:
            # unsupported shape: fall through to the XLA composition
            _note_refusal("scaled_dot_product_attention", query.shape,
                          key.shape, query.dtype, e)
    drop_key = rng_key() if (dropout_p > 0.0 and training) else None
    def _f(q, k, v, m):
        return _sdpa_ref(q, k, v, m, dropout_p, is_causal, drop_key, training)
    return apply_op("sdpa", _f, query, key, value, attn_mask)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """Parity: nn/functional/flash_attention.py:242. Returns (out, softmax)."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def _segment_ids_from_cu(cu, total):
    """cu_seqlens (B+1,) prefix sums -> per-position segment ids (total,)."""
    pos = jnp.arange(total)
    return jnp.searchsorted(cu[1:].astype(pos.dtype), pos,
                            side="right").astype(jnp.int32)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen (packed) flash attention. Parity: flash_attn_unpadded
    (reference nn/functional/flash_attention.py).

    query/key/value: (total_tokens, num_heads, head_dim) — sequences packed
    along dim 0; cu_seqlens_*: (batch+1,) int32 prefix sums. Runs the Pallas
    varlen kernel (segment-id masking with block skipping) when shapes
    allow; falls back to a masked XLA composition otherwise.
    """
    total_q, H, D = query.shape
    total_k = key.shape[0]

    def _seg_pos(cq, ck):
        """Segment ids + per-sequence causal positions. The query position
        is adjusted by the per-sequence (k_len - q_len) difference so
        causal means "key pos-in-seq <= query pos-in-seq + len_diff(seq)"
        — a single packed-global offset is wrong when the differences are
        non-uniform."""
        segq = _segment_ids_from_cu(cq, total_q)
        segk = _segment_ids_from_cu(ck, total_k)
        pq = jnp.arange(total_q) - jnp.take(cq, segq, mode="clip")
        pk = jnp.arange(total_k) - jnp.take(ck, segk, mode="clip")
        qlen = jnp.diff(cq)
        klen = jnp.diff(ck)
        ldiff = jnp.take(klen, segq, mode="clip") - jnp.take(qlen, segq,
                                                             mode="clip")
        return segq, segk, (pq + ldiff).astype(jnp.int32), pk.astype(jnp.int32)

    can_pallas = _USE_PALLAS[0] and dropout == 0.0
    if can_pallas:
        try:
            from ...kernels import flash_attention as pallas_fa
            pallas_fa.check_supported((1, total_q, H, D), (1, total_k, H, D),
                                      query.dtype)

            def _f(q, k, v, cq, ck):
                segq, segk, pq, pk = _seg_pos(cq, ck)
                return pallas_fa.flash_attention_varlen_bshd(
                    q[None], k[None], v[None], segq[None], segk[None],
                    causal=causal, sm_scale=scale, q_positions=pq[None],
                    kv_positions=pk[None])[0]

            out = apply_op("flash_attn_unpadded", _f, query, key, value,
                           cu_seqlens_q, cu_seqlens_k)
            return out, None
        except ValueError as e:
            _note_refusal("flash_attn_unpadded", query.shape, key.shape,
                          query.dtype, e)

    drop_key = rng_key() if (dropout > 0.0 and training) else None

    def _f(q, k, v, cq, ck):
        segq, segk, pq, pk = _seg_pos(cq, ck)
        allow = segq[:, None] == segk[None, :]
        if causal:
            allow = allow & (pk[None, :] <= pq[:, None])
        return _sdpa_ref(q[None], k[None], v[None], allow[None, None],
                         dropout, False, drop_key, training, scale=scale)[0]

    out = apply_op("flash_attn_unpadded", _f, query, key, value,
                   cu_seqlens_q, cu_seqlens_k)
    return out, None


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout=0.0, causal=True, window_size=None, name=None):
    """Sparse-mask attention (parity: flashmask_attention:1098).

    startend_row_indices: (B, H_or_1, S, 1|2|4) int32 — per-column row
    bounds defining the mask, as in the reference. Runs a block-sparse
    Pallas kernel that rebuilds the mask tile-by-tile from the O(S*C)
    bounds (skipping fully-masked K/V blocks for the causal document-mask
    case); falls back to a dense-mask XLA composition for unsupported
    shapes or dropout.
    """
    if window_size is not None:
        if startend_row_indices is not None:
            raise ValueError(
                "pass either window_size or startend_row_indices, not both")
        # sliding window -> flashmask bounds. Causal (left w): key col c is
        # masked for rows >= c + w + 1 (C==1). Non-causal (left, right):
        # masked for rows >= c + left + 1 or rows < c - right (C==2).
        w = window_size if isinstance(window_size, (tuple, list)) \
            else (window_size, window_size)
        sk = key.shape[1]
        b = query.shape[0]
        from ...core.tensor import Tensor
        cols = jnp.arange(sk)
        start = jnp.minimum(cols + int(w[0]) + 1, sk).astype(jnp.int32)
        if causal:
            idx = start[None, None, :, None]
            startend_row_indices = Tensor(
                jnp.broadcast_to(idx, (b, 1, sk, 1)))
        else:
            end = jnp.maximum(cols - int(w[1]), 0).astype(jnp.int32)
            idx = jnp.stack([start, end], axis=-1)[None, None]
            startend_row_indices = Tensor(
                jnp.broadcast_to(idx, (b, 1, sk, 2)))
    if startend_row_indices is None:
        return scaled_dot_product_attention(query, key, value, None, dropout,
                                            causal)
    B, Sq, H, D = query.shape
    if Sq != key.shape[1]:
        raise ValueError("flashmask_attention requires Sq == Sk (row bounds "
                         "index a square score matrix)")
    can_pallas = _USE_PALLAS[0] and dropout == 0.0
    if can_pallas:
        try:
            from ...kernels import flash_attention as pallas_fa
            pallas_fa.check_supported(tuple(query.shape), tuple(key.shape),
                                      query.dtype)
            C = startend_row_indices.shape[-1]
            if causal and C not in (1, 2):
                raise ValueError("unsupported bound count")
            if not causal and C not in (2, 4):
                raise ValueError("unsupported bound count")

            def _f(q, k, v, idx):
                return pallas_fa.flashmask_attention_bshd(q, k, v, idx,
                                                          causal=causal)

            return apply_op("flashmask_attention", _f, query, key, value,
                            startend_row_indices)
        except ValueError as e:
            _note_refusal("flashmask_attention", query.shape, key.shape,
                          query.dtype, e)

    def _build_mask(idx, sq, sk):
        # idx: (B, H, Sk, C); rows r of column c are masked per bounds
        rows = jnp.arange(sq)[None, None, :, None]  # 1,1,Sq,1
        c = idx.shape[-1]
        idxb = jnp.swapaxes(idx, 2, 3)  # B,H,C,Sk
        if causal:
            if c == 1:
                start = idxb[:, :, 0][:, :, None, :]  # B,H,1,Sk
                masked = rows >= start
            else:
                start = idxb[:, :, 0][:, :, None, :]
                end = idxb[:, :, 1][:, :, None, :]
                masked = (rows >= start) & (rows < end)
            cm = jnp.tril(jnp.ones((sq, sk), bool))
            allow = cm[None, None] & ~masked
        else:
            if c == 2:
                start_u = idxb[:, :, 0][:, :, None, :]
                end_d = idxb[:, :, 1][:, :, None, :]
                masked = (rows >= start_u) | (rows < end_d)
            else:
                start_u = idxb[:, :, 0][:, :, None, :]
                end_u = idxb[:, :, 1][:, :, None, :]
                start_d = idxb[:, :, 2][:, :, None, :]
                end_d = idxb[:, :, 3][:, :, None, :]
                masked = ((rows >= start_u) & (rows < end_u)) | \
                         ((rows >= start_d) & (rows < end_d))
            allow = ~masked
        return allow

    sq, sk = query.shape[1], key.shape[1]
    drop_key = rng_key() if dropout > 0.0 else None

    def _f(q, k, v, idx):
        allow = _build_mask(idx, sq, sk)
        # broadcast mask over heads: allow is B,H,Sq,Sk (H may be 1)
        return _sdpa_ref(q, k, v, allow, dropout, False, drop_key, True)
    return apply_op("flashmask_attention", _f, query, key, value,
                    startend_row_indices)


class sdp_kernel:
    """Context manager for kernel selection (parity: paddle sdp_kernel)."""

    def __init__(self, enable_flash=True, enable_math=True,
                 enable_mem_efficient=True):
        self.enable_flash = enable_flash
        self._prev = None

    def __enter__(self):
        self._prev = _USE_PALLAS[0]
        _USE_PALLAS[0] = self.enable_flash
        return self

    def __exit__(self, *a):
        _USE_PALLAS[0] = self._prev
        return False
