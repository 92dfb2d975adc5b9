"""Ring flash attention — context parallelism over a mesh axis.

Capability-parity-plus: the reference has no in-core ring attention (see
SURVEY.md §5 — its long-context story is Megatron-SP along TP
(`fleet/utils/sequence_parallel_utils.py`) and the `sep` topology axis
(`fleet/base/topology.py:70-90`, alltoall segment parallel); ring/blockwise
lives outside core in recipe repos). Here it is first-class and TPU-native:
K/V shards rotate around the `sep` ring with `lax.ppermute` (ICI neighbor
exchange), each hop's partial attention runs the Pallas flash kernel, and
partials merge with the standard log-sum-exp combine. The backward pass
rotates the (q, do, o, lse, dq) bundle the opposite way so dK/dV accumulate
at the K/V owner and dQ arrives home after a full loop — one ring, no
gather of the full sequence anywhere.

Causal masking is resolved at *block* granularity statically: at ring step
j, the visiting K/V block's owner is `(idx - j) mod P`, so each device picks
one of {full, diagonal, empty} via `lax.switch` — the Pallas kernels only
ever see static `causal` flags (empty blocks skip compute entirely, giving
the ~2x causal speedup ring attention is known for).

All shapes below are per-shard (inside `shard_map`): sequence length S is
the LOCAL sequence chunk.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _bwd_with_delta as _flash_step_bwd
from .flash_attention import _fwd as _flash_step_fwd
from .flash_attention import _pick_block_k, _pick_block_q, check_supported

__all__ = ["ring_flash_attention", "ulysses_attention"]


def _repeat_kv(x, rep):
    """(B*Hkv, S, D) -> (B*Hkv*rep, S, D) by repeating each head `rep`x."""
    if rep == 1:
        return x
    BH, S, D = x.shape
    return jnp.broadcast_to(x[:, None], (BH, rep, S, D)).reshape(BH * rep, S, D)


def _sum_over_rep(x, rep):
    """Inverse of _repeat_kv for gradients: sum the `rep` copies."""
    if rep == 1:
        return x
    BHr, S, D = x.shape
    return x.reshape(BHr // rep, rep, S, D).sum(axis=1)


def _combine(o_acc, l_acc, o_j, lse_j):
    """Merge a new attention partial (o_j, lse_j) into the running combined
    (o_acc f32, l_acc f32) using out = sum_j exp(lse_j - L) * o_j."""
    l_new = jnp.logaddexp(l_acc, lse_j)
    # guard exp(-inf - -inf) = nan when nothing has been visible yet
    w_prev = jnp.where(jnp.isneginf(l_new), 0.0, jnp.exp(l_acc - l_new))
    w_j = jnp.where(jnp.isneginf(l_new), 0.0, jnp.exp(lse_j - l_new))
    o_new = o_acc * w_prev[..., None] + o_j.astype(jnp.float32) * w_j[..., None]
    return o_new, l_new


def _ring_fwd(q, k, v, sm_scale, causal, axis_name, rep, block_q, block_k):
    """q: (B*H, S, D); k, v: (B*Hkv, S, D) local shards. Returns
    (out (B*H,S,D) in q.dtype, lse (B*H,S) f32)."""
    P_ = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % P_) for i in range(P_)]
    BH, S, D = q.shape

    o_acc = jnp.zeros((BH, S, D), jnp.float32)
    l_acc = jnp.full((BH, S), -jnp.inf, jnp.float32)
    kj, vj = k, v

    def step_full(q, kj, vj):
        o, lse = _flash_step_fwd(q, _repeat_kv(kj, rep), _repeat_kv(vj, rep),
                                 sm_scale, False, block_q, block_k)
        return o, lse

    def step_diag(q, kj, vj):
        o, lse = _flash_step_fwd(q, _repeat_kv(kj, rep), _repeat_kv(vj, rep),
                                 sm_scale, True, block_q, block_k)
        return o, lse

    def step_empty(q, kj, vj):
        return (jnp.zeros_like(q),
                jnp.full((BH, S), -jnp.inf, jnp.float32))

    for j in range(P_):
        if causal:
            src = (idx - j) % P_
            # keys from src visible to queries at idx: src<idx full,
            # src==idx diagonal, src>idx nothing
            rel = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
            o_j, lse_j = lax.switch(rel, [step_full, step_diag, step_empty],
                                    q, kj, vj)
        else:
            o_j, lse_j = step_full(q, kj, vj)
        o_acc, l_acc = _combine(o_acc, l_acc, o_j, lse_j)
        if j != P_ - 1:
            kj = lax.ppermute(kj, axis_name, perm)
            vj = lax.ppermute(vj, axis_name, perm)
    return o_acc.astype(q.dtype), l_acc


def _ring_bwd_loop(q, k, v, out, lse, dout, sm_scale, causal, axis_name, rep,
                   block_q, block_k):
    """Rotate the (q, do, delta, lse, dq) bundle around the ring; accumulate
    dk/dv at the local K/V owner; dq returns home after P hops. delta is
    precomputed at the query owner so the full output never travels."""
    P_ = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % P_) for i in range(P_)]
    BH, S, D = q.shape
    k_rep = _repeat_kv(k, rep)
    v_rep = _repeat_kv(v, rep)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    dk_acc = jnp.zeros(k_rep.shape, jnp.float32)
    dv_acc = jnp.zeros(v_rep.shape, jnp.float32)

    def step_full(qv, deltav, dov, lsev):
        return _flash_step_bwd(sm_scale, False, block_q, block_k,
                               qv, k_rep, v_rep, deltav, lsev, dov)

    def step_diag(qv, deltav, dov, lsev):
        return _flash_step_bwd(sm_scale, True, block_q, block_k,
                               qv, k_rep, v_rep, deltav, lsev, dov)

    def step_empty(qv, deltav, dov, lsev):
        return (jnp.zeros_like(qv), jnp.zeros_like(k_rep),
                jnp.zeros_like(v_rep))

    bundle = (q, dout, delta, lse, jnp.zeros((BH, S, D), jnp.float32))
    for j in range(P_):
        qv, dov, deltav, lsev, dq_acc = bundle
        if causal:
            src_q = (idx - j) % P_   # owner of the visiting queries
            # local keys at idx visible to visiting queries from src_q:
            # idx<src_q full, idx==src_q diagonal, idx>src_q nothing
            rel = jnp.where(idx == src_q, 1, jnp.where(idx < src_q, 0, 2))
            dq_j, dk_j, dv_j = lax.switch(
                rel, [step_full, step_diag, step_empty], qv, deltav, dov,
                lsev)
        else:
            dq_j, dk_j, dv_j = step_full(qv, deltav, dov, lsev)
        dk_acc = dk_acc + dk_j.astype(jnp.float32)
        dv_acc = dv_acc + dv_j.astype(jnp.float32)
        bundle = (qv, dov, deltav, lsev, dq_acc + dq_j.astype(jnp.float32))
        bundle = jax.tree.map(lambda t: lax.ppermute(t, axis_name, perm),
                              bundle)
    dq = bundle[4]
    dk = _sum_over_rep(dk_acc, rep)
    dv = _sum_over_rep(dv_acc, rep)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_core(q, k, v, sm_scale, causal, axis_name, rep, block_q, block_k):
    out, _ = _ring_fwd(q, k, v, sm_scale, causal, axis_name, rep,
                       block_q, block_k)
    return out


def _ring_core_fwd(q, k, v, sm_scale, causal, axis_name, rep, block_q,
                   block_k):
    out, lse = _ring_fwd(q, k, v, sm_scale, causal, axis_name, rep,
                         block_q, block_k)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(sm_scale, causal, axis_name, rep, block_q, block_k, res,
                   dout):
    q, k, v, out, lse = res
    return _ring_bwd_loop(q, k, v, out, lse, dout, sm_scale, causal,
                          axis_name, rep, block_q, block_k)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_flash_attention(q, k, v, axis_name="sep", causal=True, sm_scale=None):
    """Ring flash attention over mesh axis `axis_name` (call inside
    shard_map with q/k/v sequence-sharded on that axis).

    q: (B, S_local, H, D); k, v: (B, S_local, Hkv, D) with H % Hkv == 0.
    Global sequence order is the axis order: device i holds tokens
    [i*S_local, (i+1)*S_local). Returns (B, S_local, H, D).
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv != 0:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    rep = H // Hkv
    check_supported((B, S, H, D), (B, S, H, D), q.dtype)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    block_q = _pick_block_q(S, D)
    block_k = _pick_block_k(S, D)

    def to_flat(x):
        return jnp.swapaxes(x, 1, 2).reshape(x.shape[0] * x.shape[2],
                                             x.shape[1], x.shape[3])

    out = _ring_core(to_flat(q), to_flat(k), to_flat(v), float(sm_scale),
                     bool(causal), axis_name, int(rep), int(block_q),
                     int(block_k))
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)


def _local_attention(q, k, v, causal, sm_scale):
    """Single-device (B,S,H,D) attention: Pallas flash when shapes allow,
    else a jnp composition with fp32 softmax."""
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    try:
        from .flash_attention import flash_attention_bshd
        check_supported(tuple(q.shape), tuple(k.shape), q.dtype)
        return flash_attention_bshd(q, k, v, causal=causal, sm_scale=sm_scale)
    except ValueError:
        pass
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(cm, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ulysses_attention(q, k, v, axis_name="sep", causal=True, sm_scale=None,
                      attn_fn=None):
    """DeepSpeed-Ulysses segment parallelism: all_to_all trades the
    sequence shard for a head shard, attention runs over the full sequence
    with H/P local heads, and a second all_to_all restores seq sharding.

    Parity: the reference's `sep` axis alltoall segment parallel
    (`fleet/meta_parallel/segment_parallel.py:26` + fused attention recipes).
    q: (B, S_local, H, D), k/v: (B, S_local, Hkv, D); H must be divisible by
    the axis size (Hkv is head-repeated if needed). Differentiable through
    all_to_all — no custom vjp required.
    """
    P_ = lax.axis_size(axis_name)
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv != 0:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    if H % P_ != 0:
        raise ValueError(f"H={H} not divisible by sep={P_}")
    if Hkv % P_ != 0:
        rep = P_ // math.gcd(P_, Hkv)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def seq_to_head(x):
        # (B, S/P, H, D) -> (B, S, H/P, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qf, kf, vf = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if attn_fn is None:
        out = _local_attention(qf, kf, vf, causal, sm_scale)
    else:
        out = attn_fn(qf, kf, vf, causal=causal, sm_scale=sm_scale)
    # (B, S, H/P, D) -> (B, S/P, H, D)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)
