"""Pallas flash attention (TPU) — fwd + bwd with online softmax.

Capability parity: reference flash-attention integration
(`paddle/phi/kernels/gpu/flash_attn_kernel.cu` dynloading FA2, python API
`python/paddle/nn/functional/flash_attention.py:242` flash_attention,
`:1098` flashmask_attention, varlen `flash_attn_unpadded`). Rebuilt as a
native Pallas TPU kernel rather than a vendor-library binding.

Design (see /opt/skills/guides/pallas_guide.md):
  * layout (B, S, H, D) -> kernel works on (B*H, S, D);
  * grid (BH, q blocks, k blocks) with the k dimension innermost: K/V
    blocks stream through VMEM (Pallas double-buffers the fetches), Q and
    the fp32 accumulator stay resident in VMEM scratch across the k loop —
    no whole-K/V residency, so sequence length is HBM-bound, not VMEM-bound;
  * online softmax carries running max/denominator as (block_q, 128) fp32
    lane-broadcast scratch (TPU-legal stats layout);
  * logsumexp is emitted as (BH, 1, Sq) so its BlockSpec (1, 1, block_q)
    satisfies Mosaic's (8, 128) last-two-dims rule (second-to-last == array
    dim, last % 128 == 0 or == Sq) — validated on real v5e hardware;
  * causal runs skip fully-masked K/V blocks' compute via pl.when;
  * varlen (cu_seqlens) runs pass per-token segment ids as (B, 1, S) int32
    blocks; cross-segment scores are masked in-block and K/V blocks whose
    segment range doesn't overlap the q block's are skipped entirely;
  * flashmask runs pass the (B, Hm, Sk, C) startend_row_indices as
    (B*Hm, C, Sk) column-bound blocks — the mask is reconstructed per
    (q block, k block) tile from O(S*C) bounds, never materialized as a
    dense (B, H, Sq, Sk) tensor; for the causal C==1 (document-mask) case,
    K/V blocks that the bounds mask out completely are skipped;
  * backward = custom_vjp with a dq kernel (grid (BH, nq, nk)) and a dkv
    kernel (grid (BH, nk, nq)), recomputing probabilities from the saved
    logsumexp (no S^2 residuals).
Falls back to the XLA composition automatically when shapes don't fit
(caller: nn.functional.scaled_dot_product_attention).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_bshd", "flash_attention_varlen_bshd",
           "flashmask_attention_bshd", "flash_attention_chunk_gqa",
           "chunk_gqa_unsupported_reason"]

_INTERPRET_CACHE = [None]


def _interpret_mode():
    """Pallas interpret=True off-TPU so the same kernel runs in CPU tests."""
    if _INTERPRET_CACHE[0] is None:
        _INTERPRET_CACHE[0] = jax.default_backend() not in ("tpu",)
    return _INTERPRET_CACHE[0]


NEG_INF = np.float32(-1e30)
_STATS_LANES = 128  # lane width for the m/l running-stat scratch
_I0 = np.int32(0)   # index-map zero: the package enables x64, and Mosaic
                    # rejects i64 index-map results, so pin literals to i32


def _causal_block_mask(s, qi, ki, block_q, block_k, q_offset):
    """In-block causal mask: key pos <= query pos + q_offset."""
    bq, bk = s.shape
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(k_pos <= q_pos + q_offset, s, NEG_INF)


def _flashmask_block_mask(s, qi, ki, block_q, block_k, q_offset, fm_blk,
                          fm_causal, fm_cols):
    """Apply the flashmask column bounds to an in-block score tile.

    fm_blk: (C, block_k) int32 row bounds for this k block (reference
    startend_row_indices semantics, flash_attention.py:1098). Row indices
    are query positions; flashmask requires Sq == Sk (enforced by the
    wrapper) so the frame matches the XLA fallback exactly.
    """
    bq, bk = s.shape
    rows = (qi * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    b = fm_blk.astype(jnp.int32)
    if fm_causal:
        if fm_cols == 1:
            masked = rows >= b[0][None, :]
        else:
            masked = (rows >= b[0][None, :]) & (rows < b[1][None, :])
    else:
        if fm_cols == 2:
            masked = (rows >= b[0][None, :]) | (rows < b[1][None, :])
        else:
            masked = (((rows >= b[0][None, :]) & (rows < b[1][None, :]))
                      | ((rows >= b[2][None, :]) & (rows < b[3][None, :])))
    return jnp.where(masked, NEG_INF, s)


def _apply_masks(s, qi, ki, *, block_q, block_k, q_offset, causal,
                 segq_blk=None, segk_blk=None, posq_blk=None, posk_blk=None,
                 fm_blk=None, fm_causal=True, fm_cols=0, window=None):
    if causal and segq_blk is None:
        s = _causal_block_mask(s, qi, ki, block_q, block_k, q_offset)
    if segq_blk is not None:
        allow = segq_blk[:, None] == segk_blk[None, :]
        if causal:
            # per-sequence causal: key's position within its sequence must
            # not exceed the query's (length-difference-adjusted) position —
            # a single packed-global offset would be wrong when per-sequence
            # q/k lengths differ
            allow = jnp.logical_and(allow,
                                    posk_blk[None, :] <= posq_blk[:, None])
        if window is not None:
            # a window layer: the last `window` positions, the query's own
            # among them
            allow = jnp.logical_and(
                allow,
                posk_blk[None, :] > posq_blk[:, None] - np.int32(window))
        s = jnp.where(allow, s, NEG_INF)
    if fm_cols:
        s = _flashmask_block_mask(s, qi, ki, block_q, block_k, q_offset,
                                  fm_blk, fm_causal, fm_cols)
    return s


def _masked_exp(s, ref):
    """exp(s - ref) that yields exactly 0 for masked (-1e30) scores even
    when `ref` is itself -1e30 (row with no valid key seen yet)."""
    return jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - ref))


def _unpack_refs(refs, n_fixed, use_seg, fm_cols):
    """Split the variadic pallas ref list into (fixed inputs, segq, segk,
    fm, rest)."""
    fixed = refs[:n_fixed]
    idx = n_fixed
    segq_ref = segk_ref = fm_ref = None
    if use_seg:
        segq_ref, segk_ref = refs[idx], refs[idx + 1]
        idx += 2
    if fm_cols:
        fm_ref = refs[idx]
        idx += 1
    return fixed, segq_ref, segk_ref, fm_ref, refs[idx:]


# The block rule where segments and positions are data, stated ONCE. The
# forward reads it off every block at once (`_seg_block_table`: (nq, 1)
# against (1, nk) arrays, handed to the kernel in scalar memory); the two
# backward kernels read it off one block's own vectors, as scalars. A
# side's bounds are (segment min, segment max, position min, position max)
# of its block.
def _block_bounds(seg_blk, pos_blk):
    return (jnp.min(seg_blk), jnp.max(seg_blk),
            jnp.min(pos_blk), jnp.max(pos_blk))


def _seg_block_contributes(qb, kb, causal, window):
    """Whether a (q block, k block) tile can hold any visible score."""
    # contiguous segment ids: ranges must overlap
    contributes = jnp.logical_and(qb[0] <= kb[1], qb[1] >= kb[0])
    if causal:
        # the packed-global causal bound is invalid with per-sequence
        # alignment; skip instead when both blocks sit in one shared
        # sequence and every key position exceeds every query position
        one_seq = jnp.logical_and(qb[0] == kb[1], qb[1] == kb[0])
        all_future = kb[2] > qb[3]
        contributes = jnp.logical_and(contributes, jnp.logical_not(
            jnp.logical_and(one_seq, all_future)))
    if window is not None:
        # every key lies behind every query's window
        all_past = kb[3] <= qb[2] - np.int32(window)
        contributes = jnp.logical_and(contributes,
                                      jnp.logical_not(all_past))
    return contributes


def _seg_block_table(segq, segk, block_q, block_k, causal, window):
    """(B, nq, nk) bool: the rule above over every block of the (B, 2, S)
    [segment; position] rows, the tiles the forward computes.
    `_fwd_positions` hands the kernel this table."""
    def bounds(rows, block, axis):
        seg, pos = (rows[:, i].reshape(rows.shape[0], -1, block)
                    for i in (0, 1))
        return tuple(jnp.expand_dims(x, axis) for x in (
            seg.min(-1), seg.max(-1), pos.min(-1), pos.max(-1)))
    return _seg_block_contributes(bounds(segq, block_q, 2),
                                  bounds(segk, block_k, 1), causal, window)


def _block_contributes(qi, ki, *, block_q, block_k, q_offset, causal,
                       segq_blk, segk_blk, posq_blk=None, posk_blk=None,
                       fm_blk=None, fm_causal=True, fm_cols=0):
    """Whether this (q block, k block) tile can contain any unmasked score
    (cheap bound checks -> pl.when skips the matmuls entirely)."""
    if causal and segq_blk is None:
        contributes = ki * block_k <= qi * block_q + (block_q - 1) + q_offset
    else:
        contributes = ki >= 0
    if segq_blk is not None:
        contributes = jnp.logical_and(contributes, _seg_block_contributes(
            _block_bounds(segq_blk, posq_blk),
            _block_bounds(segk_blk, posk_blk), causal, None))
    if fm_cols == 1 and fm_causal and fm_blk is not None:
        # document mask: every row/col masked iff first q row >= max(start)
        q0 = qi * block_q
        any_open = q0 < jnp.max(fm_blk[0])
        contributes = jnp.logical_and(contributes, any_open)
    return contributes


def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, nk, q_offset,
                fm_causal, fm_cols):
    """The forward where the mask is a function of the tile's place (plain
    causal: the train step's) or of flashmask columns. Segments and
    positions as data have a kernel of their own, `_fwd_positions_kernel`."""
    sm_scale = np.float32(sm_scale)  # strong f32: x64 mode makes bare
    # python/np floats f64, which Mosaic cannot store into f32 refs
    (q_ref, k_ref, v_ref), _, _, fm_ref, rest = _unpack_refs(
        refs, 3, False, fm_cols)
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    masked_rows = fm_cols  # rows may see no valid key yet

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    fm_blk = fm_ref[0] if fm_cols else None
    contributes = _block_contributes(
        qi, ki, block_q=block_q, block_k=block_k, q_offset=q_offset,
        causal=causal, segq_blk=None, segk_blk=None, fm_blk=fm_blk,
        fm_causal=fm_causal, fm_cols=fm_cols)

    @pl.when(contributes)
    def _step():
        q = q_ref[0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = _apply_masks(s, qi, ki, block_q=block_q, block_k=block_k,
                         q_offset=q_offset, causal=causal, fm_blk=fm_blk,
                         fm_causal=fm_causal, fm_cols=fm_cols)
        m_prev = m_ref[:, :1]                      # (bq, 1), lanes equal
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = _masked_exp(s, m_new) if masked_rows else jnp.exp(s - m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_new)) if masked_rows else \
            jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.maximum(l, np.float32(1e-30))
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(safe_l[:, 0])


def _extra_in_specs(B, H, Sq, Sk, block_q, block_k, use_seg, fm_cols, fm_heads,
                    kmajor=False):
    """BlockSpecs for the optional segment-id / flashmask inputs.

    Grid order is (bh, i=q block, j=k block) — or (bh, j, i) for the dkv
    kernel (kmajor=True)."""
    specs = []
    if kmajor:
        def qmap(idx):
            return lambda b, j, i, _f=idx: _f(b, i, j)
    else:
        def qmap(idx):
            return idx

    def bdiv(b):
        # b // H via lax.div (b >= 0): jnp floor-division lowers through an
        # i64 convert under x64, which Mosaic cannot lower (infinite
        # recursion in its convert fallback — found on real v5e)
        return jax.lax.div(b, jnp.asarray(H, jnp.int32))

    if use_seg:
        # rows: [segment id, causal position-within-sequence]
        specs.append(pl.BlockSpec(
            (1, 2, block_q), qmap(lambda b, i, j: (bdiv(b), _I0, i))))
        specs.append(pl.BlockSpec(
            (1, 2, block_k), qmap(lambda b, i, j: (bdiv(b), _I0, j))))
    if fm_cols:
        if fm_heads == 1:
            specs.append(pl.BlockSpec(
                (1, fm_cols, block_k),
                qmap(lambda b, i, j: (bdiv(b), _I0, j))))
        else:
            specs.append(pl.BlockSpec(
                (1, fm_cols, block_k), qmap(lambda b, i, j: (b, _I0, j))))
    return specs


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, fm=None,
         fm_causal=True, H=1):
    """(BH, Sq, D) x (BH, Sk, D)^2 -> out (BH, Sq, D), lse (BH, Sq) f32.

    fm: optional (B*Hm, C, Sk) flashmask bounds. (Segment ids and
    positions as data: `_fwd_positions`.)"""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    nq = Sq // block_q
    nk = Sk // block_k
    grid = (BH, nq, nk)
    fm_cols = fm.shape[1] if fm is not None else 0
    fm_heads = (fm.shape[0] * H) // BH if fm is not None else 1
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, nk=nk, q_offset=Sk - Sq, fm_causal=fm_causal,
        fm_cols=fm_cols)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, _I0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, _I0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, _I0)),
    ] + _extra_in_specs(BH // H, H, Sq, Sk, block_q, block_k, False,
                        fm_cols, fm_heads)
    args = [q, k, v]
    if fm_cols:
        args.append(fm)
    out, lse3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, _I0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret_mode(),
        name="flash_attention_fwd",
    )(*args)
    return out, lse3[:, 0, :]


# ------------------------------------------- forward, positions are data
# The block table's room: a word a tile over the batch rows, in half of a
# v5e's 1 MiB of scalar memory (a table of 2^18 words is refused by the
# compiler; 2^17 is 131,072 packed tokens a row at tiles of 1,024, two
# such rows at 512).
_TABLE_TILES = 1 << 17


def _fwd_positions_kernel(table_ref, span_ref, q_ref, k_ref, v_ref, segq_ref,
                          segk_ref, o_ref, *rest, sm_scale, causal, block_q,
                          block_k, nq, nk, H, window):
    """`_fwd_kernel` where segments and positions are data (every serving
    chunk; nothing a train step runs). Whether a tile is computed is read
    from `table_ref` (`_seg_block_table`, in scalar memory). Both products
    take their operands in the dtype they are stored in and accumulate in
    float32; max, sum and the accumulator are float32."""
    del span_ref                                   # the index maps' own
    *lse_ref, acc_ref, m_ref, l_ref = rest         # lse: only if asked for
    sm_scale = np.float32(sm_scale)                # strong f32, as above
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row = jax.lax.div(pl.program_id(0), np.int32(H)) * np.int32(nq) + qi

    @pl.when(table_ref[row * np.int32(nk) + ki] != 0)
    def _step():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]     # (bq, D), (bk, D) x 2
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _apply_masks(
            s * sm_scale, qi, ki, block_q=block_q, block_k=block_k,
            q_offset=0, causal=causal, segq_blk=segq_ref[0, 0],
            segk_blk=segk_ref[0, 0], posq_blk=segq_ref[0, 1],
            posk_blk=segk_ref[0, 1], window=window)
        m_prev = m_ref[:, :1]                      # (bq, 1), lanes equal
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = _masked_exp(s, m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_new))
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.maximum(l, np.float32(1e-30))
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        if lse_ref:             # a column laid along lanes: not for nothing
            lse_ref[0][0, 0] = m_ref[:, 0] + jnp.log(safe_l[:, 0])


def _fwd_positions(q, k, v, sm_scale, causal, block_q, block_k, seg, H,
                   window=None, with_lse=True):
    """`_fwd` where segments and positions are data (`_fwd_positions_once`
    has the arguments): traced and lowered ONCE a program, however many
    layers call it with the same shapes. A serving program calls it a
    layer, and without the inner jit each call's trace and Mosaic lowering
    are paid again (Kimi's chunk programs of seven calls: 5.6 s a program
    against 2.4, PR 37). Raises ValueError where the block table would not
    fit in scalar memory."""
    tiles = (q.shape[0] // H) * (q.shape[1] // block_q) * (
        k.shape[1] // block_k)
    if tiles > _TABLE_TILES:
        raise ValueError(
            f"{tiles} (q block, k block) tiles of {block_q} x {block_k}: "
            f"the block table holds {_TABLE_TILES} in scalar memory")
    return _fwd_positions_once(
        q, k, v, seg, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, H=H, window=window, with_lse=with_lse,
        interpret=_interpret_mode())


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "causal", "block_q", "block_k", "H", "window", "with_lse",
    "interpret"))
def _fwd_positions_once(q, k, v, seg, *, sm_scale, causal, block_q, block_k,
                        H, window, with_lse, interpret):
    """`_fwd` where segments and positions are data: `seg` is (segq (B, 2,
    Sq), segk (B, 2, Sk)) int32 [segment id; causal position-within-
    sequence] rows. window: optional int, with `causal`: a query also sees
    no key `window` or more positions behind it (forward only:
    `flash_attention_chunk_gqa`). with_lse=False, where nothing will be
    differentiated, returns None for lse and the kernel does not work it
    out (a column laid along lanes, a q block at a time).

    The block rule is worked out ONCE, outside the kernel, over every
    tile (`_seg_block_table`) and handed over in scalar memory: the
    kernel reads one word instead of reducing four vectors to scalars a
    step, and the K/V (and key-row) fetches of a q block are
    clamped to the first and last tile it computes, so the skipped tiles
    before and after them (a chunk's future, the keys behind its window,
    the table past the sequence's end) are not fetched at all: the
    pipeline fetches nothing where the block index does not change."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // block_q, Sk // block_k
    segq, segk = seg
    contributes = _seg_block_table(segq, segk, block_q, block_k, causal,
                                   window)
    first = jnp.argmax(contributes, axis=-1).astype(jnp.int32)
    last = np.int32(nk - 1) - jnp.argmax(
        contributes[..., ::-1], axis=-1).astype(jnp.int32)
    span = jnp.stack([first, last], axis=-1)       # (B, nq, 2)
    # The index maps are evaluated some steps past the last one (PR 28:
    # `kernels/paged_attention.py`): `span` ends in zeros enough for a
    # whole further row of q blocks, and `kblock` clips whatever it reads.
    table, span = (jnp.pad(a.reshape(-1), (0, -a.size % 128 + tail))
                   for a, tail in ((contributes.astype(jnp.int32), 128),
                                   (span, 128 * (1 + nq // 64))))

    def bdiv(b):
        return jax.lax.div(b, np.int32(H))         # as `_extra_in_specs`

    def kblock(b, i, j, span):
        at = (bdiv(b) * np.int32(nq) + i) * np.int32(2)
        j = jnp.minimum(jnp.maximum(j, span[at]), span[at + np.int32(1)])
        return jnp.clip(j, _I0, np.int32(nk - 1))

    kernel = functools.partial(
        _fwd_positions_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, nq=nq, nk=nk, H=H, window=window)
    kv_spec = pl.BlockSpec(
        (1, block_k, D), lambda b, i, j, table, span: (
            b, kblock(b, i, j, span), _I0))
    out, *lse3 = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j, *_: (b, i, _I0)),
                kv_spec, kv_spec,
                # rows: [segment id, causal position-within-sequence]
                pl.BlockSpec((1, 2, block_q),
                             lambda b, i, j, *_: (bdiv(b), _I0, i)),
                pl.BlockSpec((1, 2, block_k),
                             lambda b, i, j, table, span: (
                                 bdiv(b), _I0, kblock(b, i, j, span))),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j, *_: (b, i, _I0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j, *_: (b, _I0, i)),
            ][:1 + with_lse],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
                pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ][:1 + with_lse],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(table, span, q, k, v, segq, segk)
    return out, (lse3[0][:, 0, :] if with_lse else None)


def _dq_kernel(*refs, sm_scale, causal, block_q, block_k, nk, q_offset,
               use_seg, fm_causal, fm_cols):
    sm_scale = np.float32(sm_scale)  # strong f32: x64 mode makes bare
    # python/np floats f64, which Mosaic cannot store into f32 refs
    (q_ref, k_ref, v_ref, delta_ref, do_ref, lse_ref), segq_ref, segk_ref, \
        fm_ref, rest = _unpack_refs(refs, 6, use_seg, fm_cols)
    dq_ref, dq_acc_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    masked_rows = use_seg or fm_cols

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    segq_blk = segq_ref[0, 0] if use_seg else None
    posq_blk = segq_ref[0, 1] if use_seg else None
    segk_blk = segk_ref[0, 0] if use_seg else None
    posk_blk = segk_ref[0, 1] if use_seg else None
    fm_blk = fm_ref[0] if fm_cols else None
    contributes = _block_contributes(
        qi, ki, block_q=block_q, block_k=block_k, q_offset=q_offset,
        causal=causal, segq_blk=segq_blk, segk_blk=segk_blk,
        posq_blk=posq_blk, posk_blk=posk_blk, fm_blk=fm_blk,
        fm_causal=fm_causal, fm_cols=fm_cols)

    @pl.when(contributes)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        delta = delta_ref[0, 0][:, None]           # (bq, 1)
        lse = lse_ref[0, 0][:, None]               # (bq, 1)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = _apply_masks(s, qi, ki, block_q=block_q, block_k=block_k,
                         q_offset=q_offset, causal=causal, segq_blk=segq_blk,
                         segk_blk=segk_blk, posq_blk=posq_blk,
                         posk_blk=posk_blk, fm_blk=fm_blk,
                         fm_causal=fm_causal, fm_cols=fm_cols)
        p = _masked_exp(s, lse) if masked_rows else jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, causal, block_q, block_k, nq, q_offset,
                use_seg, fm_causal, fm_cols):
    sm_scale = np.float32(sm_scale)  # strong f32: x64 mode makes bare
    # python/np floats f64, which Mosaic cannot store into f32 refs
    (q_ref, k_ref, v_ref, delta_ref, do_ref, lse_ref), segq_ref, segk_ref, \
        fm_ref, rest = _unpack_refs(refs, 6, use_seg, fm_cols)
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    masked_rows = use_seg or fm_cols

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    segq_blk = segq_ref[0, 0] if use_seg else None
    posq_blk = segq_ref[0, 1] if use_seg else None
    segk_blk = segk_ref[0, 0] if use_seg else None
    posk_blk = segk_ref[0, 1] if use_seg else None
    fm_blk = fm_ref[0] if fm_cols else None
    # same skip predicate as fwd/dq: the causal bound "k block start <= q
    # block end (+offset)" is symmetric in the two grid orders
    contributes = _block_contributes(
        qi, ki, block_q=block_q, block_k=block_k, q_offset=q_offset,
        causal=causal, segq_blk=segq_blk, segk_blk=segk_blk,
        posq_blk=posq_blk, posk_blk=posk_blk, fm_blk=fm_blk,
        fm_causal=fm_causal, fm_cols=fm_cols)

    @pl.when(contributes)
    def _step():
        k = k_ref[0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)           # (bq, D)
        do = do_ref[0].astype(jnp.float32)
        delta = delta_ref[0, 0][:, None]
        lse = lse_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = _apply_masks(s, qi, ki, block_q=block_q, block_k=block_k,
                         q_offset=q_offset, causal=causal, segq_blk=segq_blk,
                         segk_blk=segk_blk, posq_blk=posq_blk,
                         posk_blk=posk_blk, fm_blk=fm_blk,
                         fm_causal=fm_causal, fm_cols=fm_cols)
        p = _masked_exp(s, lse) if masked_rows else jnp.exp(s - lse)
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, res, dout, seg=None, fm=None,
         fm_causal=True, H=1):
    q, k, v, out, lse = res
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    return _bwd_with_delta(sm_scale, causal, block_q, block_k,
                           q, k, v, delta, lse, dout, seg=seg, fm=fm,
                           fm_causal=fm_causal, H=H)


def _bwd_with_delta(sm_scale, causal, block_q, block_k, q, k, v, delta, lse,
                    dout, seg=None, fm=None, fm_causal=True, H=1):
    """delta: (BH, Sq) f32 = sum(dout*out, -1) — precomputed so callers
    (e.g. ring attention) need not carry the full output tensor."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    q_offset = Sk - Sq
    nq = Sq // block_q
    nk = Sk // block_k
    delta3 = delta[:, None, :]                     # (BH, 1, Sq)
    lse3 = lse[:, None, :]
    use_seg = seg is not None
    fm_cols = fm.shape[1] if fm is not None else 0
    fm_heads = (fm.shape[0] * H) // BH if fm is not None else 1
    B = BH // H

    extra_args = []
    if use_seg:
        extra_args += [seg[0], seg[1]]
    if fm_cols:
        extra_args.append(fm)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          q_offset=q_offset, use_seg=use_seg,
                          fm_causal=fm_causal, fm_cols=fm_cols),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, _I0, i)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, _I0, i)),
        ] + _extra_in_specs(B, H, Sq, Sk, block_q, block_k, use_seg, fm_cols,
                            fm_heads),
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, _I0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret_mode(),
        name="flash_attention_bwd_dq",
    )(q, k, v, delta3, dout, lse3, *extra_args)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          q_offset=q_offset, use_seg=use_seg,
                          fm_causal=fm_causal, fm_cols=fm_cols),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, _I0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, _I0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, _I0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, _I0, i)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, _I0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, _I0, i)),
        ] + _extra_in_specs(B, H, Sq, Sk, block_q, block_k, use_seg, fm_cols,
                            fm_heads, kmajor=True),
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, _I0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret_mode(),
        name="flash_attention_bwd_dkv",
    )(q, k, v, delta3, dout, lse3, *extra_args)
    return dq, dk, dv


# ------------------------------------------------------------- plain core
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, sm_scale, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k)
    return out


def _flash_core_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(sm_scale, causal, block_q, block_k, res, dout):
    return _bwd(sm_scale, causal, block_q, block_k, res, dout)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _int_zero(x):
    """float0 cotangent for integer primal inputs of custom_vjp rules."""
    return np.zeros(x.shape, jax.dtypes.float0)


# ----------------------------------------------------------- varlen core
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_core_seg(q, k, v, segq, segk, sm_scale, causal, block_q, block_k,
                    H):
    out, _ = _fwd_positions(q, k, v, sm_scale, causal, block_q, block_k,
                            (segq, segk), H, with_lse=False)
    return out


def _flash_core_seg_fwd(q, k, v, segq, segk, sm_scale, causal, block_q,
                        block_k, H):
    out, lse = _fwd_positions(q, k, v, sm_scale, causal, block_q, block_k,
                              (segq, segk), H)
    return out, (q, k, v, out, lse, segq, segk)


def _flash_core_seg_bwd(sm_scale, causal, block_q, block_k, H, res, dout):
    q, k, v, out, lse, segq, segk = res
    dq, dk, dv = _bwd(sm_scale, causal, block_q, block_k,
                      (q, k, v, out, lse), dout, seg=(segq, segk), H=H)
    return dq, dk, dv, _int_zero(segq), _int_zero(segk)


_flash_core_seg.defvjp(_flash_core_seg_fwd, _flash_core_seg_bwd)


# -------------------------------------------------------- flashmask core
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core_fm(q, k, v, fm, sm_scale, causal, block_q, block_k,
                   fm_causal, H):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, fm=fm,
                  fm_causal=fm_causal, H=H)
    return out


def _flash_core_fm_fwd(q, k, v, fm, sm_scale, causal, block_q, block_k,
                       fm_causal, H):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, fm=fm,
                    fm_causal=fm_causal, H=H)
    return out, (q, k, v, out, lse, fm)


def _flash_core_fm_bwd(sm_scale, causal, block_q, block_k, fm_causal, H,
                       res, dout):
    q, k, v, out, lse, fm = res
    dq, dk, dv = _bwd(sm_scale, causal, block_q, block_k,
                      (q, k, v, out, lse), dout, fm=fm, fm_causal=fm_causal,
                      H=H)
    return dq, dk, dv, _int_zero(fm)


_flash_core_fm.defvjp(_flash_core_fm_fwd, _flash_core_fm_bwd)


def _pick_block(n, target):
    """Pick a block along a sequence axis: either the whole axis (always
    legal — BlockSpec dims equal to the array dims pass the Mosaic (8,128)
    rule) or a divisor that is a multiple of 128. The 128 constraint comes
    from the q axis, whose (1, 1, block_q) lse/delta specs put block_q in
    the lane dimension; k blocks share the same picker so both stay
    MXU-tile aligned."""
    if n <= target or n % 128 != 0:
        return n
    b = target
    while n % b != 0:
        b -= 128
    return max(b, 128)


def _block_cap(d):
    """The largest tile side for a head width `d`: 1,024 up to 128 (the
    sweep below); 512 above it, where the 1,024 x 1,024 float32 score tile
    does not fit in a v5e's VMEM beside the wider q/k/v tiles and a whole
    program's other fusions (PR 31, d 192: the whole-program rehearsal
    refuses it, a compile of the kernel alone does not)."""
    return 1024 if d <= 128 else 512


def _pick_block_q(sq, d=128):
    """Default (1024, 1024): the on-chip block sweeps (v5e; S∈{2048,
    8192}, D∈{64, 128}, causal; fwd and fwd+bwd; device-side timing)
    found it fastest at every shape tried — 1.5-1.9× over the original
    (256, 512) defaults. Bigger tiles amortize the per-block
    online-softmax bookkeeping and keep the MXU fed; VMEM stays under
    budget (k+v tiles at 1024×128 bf16 = 512 KB, scores 1024×1024 fp32
    = 4 MB). (2048, 2048) fails to compile (VMEM); (1024, 2048)
    regresses fwd badly — don't chase full-axis K. A head width `d` over
    128 caps both at 512 (`_block_cap`)."""
    return _pick_block(sq, _block_cap(d))


def _pick_block_k(sk, d=128):
    return _pick_block(sk, _block_cap(d))


def unsupported_reason(q_shape, k_shape, dtype):
    """Why the kernel refuses these (B, S, H, D) shapes, or None where it
    takes them: the one statement of its tiling rule. K/V stream through
    VMEM in blocks, so sequence length is not VMEM-bound; only tiling
    legality is checked."""
    B, Sq, H, D = q_shape
    Sk = k_shape[1]
    if D > 256 or D % 8 != 0:
        return f"head_dim {D} unsupported"
    if Sq % 8 != 0 or Sk % 8 != 0:
        return "seq len must be multiple of 8"
    if Sq % 128 != 0 and Sq > 1024:
        return "long Sq must be a multiple of 128"
    if Sk % 128 != 0 and Sk > 1024:
        return "long Sk must be a multiple of 128"
    return None


def check_supported(q_shape, k_shape, dtype):
    """Raises ValueError for shapes the kernel doesn't support (caller falls
    back to the XLA composition)."""
    why = unsupported_reason(q_shape, k_shape, dtype)
    if why is not None:
        raise ValueError(why)


def _to_bhsd(x):
    return jnp.swapaxes(x, 1, 2).reshape(x.shape[0] * x.shape[2],
                                         x.shape[1], x.shape[3])


def _from_bhsd(out, B, H, Sq, D):
    return jnp.swapaxes(out.reshape(B, H, Sq, D), 1, 2)


def flash_attention_bshd(q, k, v, causal=False, sm_scale=None):
    """q,k,v: (B, S, H, D) -> out (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    check_supported(tuple(q.shape), tuple(k.shape), q.dtype)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    block_q = _pick_block_q(Sq, D)
    block_k = _pick_block_k(Sk, D)
    qf, kf, vf = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    from .autotune import autotune_enabled, lookup
    sig = (B * H, Sq, Sk, D, str(q.dtype), bool(causal))
    if autotune_enabled() and not _interpret_mode() \
            and not isinstance(q, jax.core.Tracer):
        # eager concrete inputs on real TPU: search the legal block grid
        # once per (shape, device) and reuse the cached winner
        from .autotune import attention_block_candidates, autotune

        def run(cfg):
            bq, bk = int(cfg["block_q"]), int(cfg["block_k"])
            return (lambda a, b, c: _flash_core(
                a, b, c, float(sm_scale), bool(causal), bq, bk),
                (qf, kf, vf))

        best = autotune("flash_fwd", sig,
                        attention_block_candidates(Sq, Sk), run,
                        default={"block_q": block_q, "block_k": block_k})
        block_q, block_k = best["block_q"], best["block_k"]
    elif autotune_enabled():
        # trace time (jitted models) with the flag on: shapes are
        # static, so a previously persisted winner still applies.
        # Gated on the flag — with autotune off, heuristics stand (a
        # stale cache must not silently override retuned defaults).
        hit = lookup("flash_fwd", sig)
        if hit is not None:
            block_q, block_k = int(hit["block_q"]), int(hit["block_k"])
    out = _flash_core(qf, kf, vf, float(sm_scale),
                      bool(causal), int(block_q), int(block_k))
    return _from_bhsd(out, B, H, Sq, D)


def _positions_in_segments(seg):
    """Per-token position within its (contiguous) segment: (B, S) -> (B, S).
    pos[p] = p - start_of_segment(p), via a cumulative max over boundary
    indices."""
    B, S = seg.shape
    p = jnp.arange(S, dtype=jnp.int32)[None, :]
    boundary = jnp.where(seg != jnp.roll(seg, 1, axis=1), p, 0)
    boundary = boundary.at[:, 0].set(0)
    start = jax.lax.cummax(boundary, axis=1)
    return p - start


def flash_attention_varlen_bshd(q, k, v, q_segment_ids, kv_segment_ids,
                                causal=False, sm_scale=None,
                                q_positions=None, kv_positions=None):
    """Varlen (packed) flash attention via per-token segment ids.

    q,k,v: (B, S, H, D); segment ids: (B, Sq)/(B, Sk) int32 — tokens attend
    only within their segment (the cu_seqlens formulation of the reference's
    flash_attn_unpadded packs sequences along S; nn.functional converts
    cu_seqlens to segment ids). K/V blocks with no segment overlap are
    skipped.

    Causal masking is PER-SEQUENCE: key position-within-sequence <= query
    position-within-sequence (positions derived from the segment ids, or
    passed explicitly via q_positions/kv_positions — flash_attn_unpadded
    adjusts q positions by the per-sequence k/q length difference for
    cross-attention packing)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    check_supported(tuple(q.shape), tuple(k.shape), q.dtype)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    block_q = _pick_block_q(Sq, D)
    block_k = _pick_block_k(Sk, D)
    ids_q = q_segment_ids.astype(jnp.int32).reshape(B, Sq)
    ids_k = kv_segment_ids.astype(jnp.int32).reshape(B, Sk)
    if causal:
        pos_q = (q_positions.astype(jnp.int32).reshape(B, Sq)
                 if q_positions is not None else _positions_in_segments(ids_q))
        pos_k = (kv_positions.astype(jnp.int32).reshape(B, Sk)
                 if kv_positions is not None
                 else _positions_in_segments(ids_k))
    else:
        pos_q = jnp.zeros((B, Sq), jnp.int32)
        pos_k = jnp.zeros((B, Sk), jnp.int32)
    segq = jnp.stack([ids_q, pos_q], axis=1)       # (B, 2, Sq)
    segk = jnp.stack([ids_k, pos_k], axis=1)
    out = _flash_core_seg(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), segq, segk,
                          float(sm_scale), bool(causal), int(block_q),
                          int(block_k), int(H))
    return _from_bhsd(out, B, H, Sq, D)


def chunk_gqa_unsupported_reason(s, t, heads, kv_heads, d, dtype):
    """Why `flash_attention_chunk_gqa` refuses a chunk of `s` queries of
    `heads` heads over `t` keys of `kv_heads`, or None: the kernel's
    tiling rule over the folded query axis."""
    if heads % kv_heads:
        return f"{heads} heads are not whole groups of {kv_heads} KV heads"
    return unsupported_reason((1, s * (heads // kv_heads), 1, d),
                              (1, t, 1, d), dtype)


def _chunk_gqa_keys(t, d):
    """The count of keys `flash_attention_chunk_gqa` runs over `t` gathered
    ones: `t`, or the next count, 128 at a time, that divides into key
    tiles of half the largest or more. (A window of 4,096 and a chunk of
    512 or 1,024 tokens gather 37 and 41 x 128 keys, which divide into
    128-key tiles only: such a call took longer than the 2,048-token
    chunk's, PR 37.)"""
    while _pick_block_k(t, d) < min(_block_cap(d) // 2, t):
        t += 128
    return t


def flash_attention_chunk_gqa(q, k, v, q_positions, kv_positions, *,
                              sm_scale=None, window=None):
    """ONE sequence's prefill chunk over its gathered keys, grouped-query
    heads, forward only: q (S, H, D) at `q_positions` (S,), k and v
    (T, KVH, D) at `kv_positions` (T,); a query sees the keys at
    positions <= its own and, with `window`, > its own - window. Returns
    (S, H, D).

    The G = H / KVH query heads of a KV head are laid along the query
    axis, (KVH, S * G, D) against (KVH, T, D), so K and V are never
    repeated G times (at 128 query heads over 8 that is 16 x a 13,056-key
    table a layer). A position's G heads lie side by side, so a q block
    of 1,024 rows spans 1,024 / G positions and few of its key blocks
    straddle the causal or the window's edge. Positions are data, as in
    the varlen form: key blocks wholly in a query block's future, or
    wholly behind its window, are neither computed nor fetched
    (`_fwd_positions`); where `T` tiles badly the keys are padded with
    such blocks' kind, keys in every query's future (`_chunk_gqa_keys`)."""
    S, H, D = q.shape
    T, KVH, _ = k.shape
    G = H // KVH
    why = chunk_gqa_unsupported_reason(S, T, H, KVH, D, q.dtype)
    if why is not None:
        raise ValueError(why)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    pad = _chunk_gqa_keys(T, D) - T
    qf = jnp.swapaxes(q.reshape(S, KVH, G, D), 0, 1).reshape(KVH, S * G, D)
    kf, vf = (jnp.pad(jnp.swapaxes(x, 0, 1), ((0, 0), (0, pad), (0, 0)))
              for x in (k, v))
    qpos = jnp.repeat(q_positions.astype(jnp.int32), G)
    kpos = jnp.pad(kv_positions.astype(jnp.int32), (0, pad),
                   constant_values=np.iinfo(np.int32).max)
    # rows: [segment id, position]; one segment
    seg = (jnp.stack([jnp.ones_like(qpos), qpos])[None],
           jnp.stack([jnp.ones_like(kpos), kpos])[None])
    out, _ = _fwd_positions(
        qf, kf, vf, float(sm_scale), True, int(_pick_block_q(S * G, D)),
        int(_pick_block_k(T + pad, D)), seg, KVH, window=window,
        with_lse=False)
    return jnp.swapaxes(out.reshape(KVH, S, G, D), 0, 1).reshape(S, H, D)


def flashmask_attention_bshd(q, k, v, startend_row_indices, causal=True,
                             sm_scale=None):
    """Block-sparse flashmask attention (parity: flashmask_attention:1098).

    startend_row_indices: (B, 1|H, Sk, C) int32 with C in {1, 2} (causal)
    or {2, 4} (non-causal) — per-key-column masked row ranges. The mask is
    reconstructed tile-by-tile inside the kernel from O(S*C) bounds; no
    dense (B, H, Sq, Sk) tensor is ever built."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    check_supported(tuple(q.shape), tuple(k.shape), q.dtype)
    if Sq != Sk:
        # bounds are query-row indices in a square score matrix; the XLA
        # fallback defines the same frame, so reject rectangles identically
        raise ValueError("flashmask requires Sq == Sk")
    Hm = startend_row_indices.shape[1]
    C = startend_row_indices.shape[3]
    if Hm not in (1, H):
        raise ValueError(f"flashmask heads dim {Hm} must be 1 or {H}")
    if causal and C not in (1, 2):
        raise ValueError("causal flashmask needs 1 or 2 bound columns")
    if not causal and C not in (2, 4):
        raise ValueError("non-causal flashmask needs 2 or 4 bound columns")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    block_q = _pick_block_q(Sq, D)
    block_k = _pick_block_k(Sk, D)
    # (B, Hm, Sk, C) -> (B*Hm, C, Sk)
    fm = jnp.swapaxes(startend_row_indices.astype(jnp.int32), 2, 3)
    fm = fm.reshape(B * Hm, C, Sk)
    out = _flash_core_fm(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), fm,
                         float(sm_scale), bool(causal), int(block_q),
                         int(block_k), bool(causal), int(H))
    return _from_bhsd(out, B, H, Sq, D)
