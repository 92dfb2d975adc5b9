"""Kimi-K2 (DeepSeek-V3's layer, key for key): multi-head latent attention
over a one-pool paged cache, and sigmoid-routed experts with a shared
expert, served as one chip's SHARE of an expert-parallel deployment.

Capability parity: the reference serves this family through its fused MLA
and MoE kernel packs (`paddle/phi/kernels/fusion/gpu/` block attention and
`python/paddle/incubate/nn/functional/` moe dispatch / ffn / reduce);
rebuilt here over the engine's paged contract (`models/paged.py`).

One layer, as published (`KimiK2Config` carries the config.json keys):

  attention  c_q = RMSNorm(x W_qa); q = c_q W_qb, per head q_nope | q_pe;
             [c_kv | k_pe] = x W_kva; c_kv = RMSNorm(c_kv); k_pe =
             RoPE(k_pe), one for all heads; q_pe = RoPE(q_pe); [k_nope |
             v] = c_kv W_kvb per head; scores (q_nope . k_nope + q_pe .
             k_pe) x (d_nope + d_rope)^-0.5 x m^2, m = 0.1 x
             mscale_all_dim x ln(factor) + 1; causal softmax in float32;
             o = concat(P v) W_o. RoPE is YaRN.
  cache      per token and layer `c_kv` after its norm and `k_pe` after
             RoPE: kv_lora_rank + qk_rope_head_dim values (576), ONE
             array a layer of (pages, page, 640): the entry padded to
             whole lane tiles (`kernels/mla_attention.py` says why). A
             prefill chunk and a verify span attend in the EXPANDED form
             over the gathered entries (compute-bound; a chunk through
             the flash kernel); a decode step in the ABSORBED form
             over the entries themselves (`kernels/mla_attention.py`):
             q_nope W_kvb^K against c_kv, P c_kv through W_kvb^V.
  experts    s = sigmoid(x W_g) in float32 over all n_routed_experts; the
             top k of s + e_score_correction_bias (n_group 1: plain
             top-k); weights s at the chosen (without the bias), divided
             by their sum, times routed_scaling_factor; y = sum_i w_i
             E_i(x) + Shared(x). The first `first_k_dense_replace` layers
             are a dense SwiGLU. No token is dropped at any load.
  the share  an expert layer is told which experts it holds
             (`experts_held` contiguous from `expert_offset`), routes over
             ALL of them, and adds only its own experts' part and the
             shared expert; that partial sum goes on to the next layer.
             No code stands in for the absent chips or their exchange.
             The held experts' products run over ragged groups sorted by
             expert (`kernels/grouped_matmul.py`).

One departure, shared with the benchmark's reference: RoPE pairs lanes
interleaved (2i, 2i + 1), as `models/llama.py` does; the published code
re-lays the same pairs out in halves before rotating, which leaves every
q_pe . k_pe unchanged.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.mpu import (ColumnParallelLinear,
                                     VocabParallelEmbedding)
from ..nn.initializer import Constant, Normal
from ..ops.dispatch import apply_op
from .llama import LlamaMLP
from .paged import PagedCacheSpec

__all__ = ["KimiK2Config", "KimiK2ForCausalLM", "kimi_k2_tiny",
           "yarn_rope_tables", "yarn_softmax_scale", "PAGED_COUNTERS"]

# what an expert layer counts, summed over the layers of a launch (the
# engine adds each to its metrics counter of the same name)
PAGED_COUNTERS = ("moe_pairs_routed", "moe_pairs_held",
                  "moe_experts_touched", "moe_max_expert_pairs")


@dataclass
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096})
    max_position_embeddings: int = 262144
    # the share: how many routed experts THIS chip holds, from which
    # (None: all of them, the uncut layer)
    experts_held: Optional[int] = None
    expert_offset: int = 0

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else int(self.experts_held))


def kimi_k2_tiny(**kw):
    """The same layer at toy widths (tests; widths the kernels accept)."""
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_hidden_layers=3,
               num_attention_heads=8, q_lora_rank=48, kv_lora_rank=128,
               qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
               n_routed_experts=32, num_experts_per_tok=4,
               max_position_embeddings=256,
               rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                             "beta_slow": 1, "mscale": 1,
                             "mscale_all_dim": 1,
                             "original_max_position_embeddings": 64})
    cfg.update(kw)
    return KimiK2Config(**cfg)


# ------------------------------------------------------------------- YaRN
def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_softmax_scale(cfg) -> float:
    """(d_nope + d_rope)^-0.5 x m^2, m = 0.1 x mscale_all_dim x
    ln(factor) + 1."""
    rs = cfg.rope_scaling or {}
    m = _yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0))
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def yarn_rope_tables(cfg):
    """cos and sin (max_position_embeddings, d_rope / 2) in float32: below
    `beta_slow` rotations over the original context a frequency is
    divided by `factor`, above `beta_fast` kept, between them blended by
    a linear ramp; the tables are scaled by mscale / mscale_all_dim."""
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    rs = cfg.rope_scaling or {}
    factor = float(rs.get("factor", 1))
    extra = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    inv = extra
    scale = 1.0
    if factor > 1:
        orig = rs["original_max_position_embeddings"]

        def corr(rot):
            return d * math.log(orig / (rot * 2 * math.pi)) \
                / (2 * math.log(base))

        low = max(math.floor(corr(rs["beta_fast"])), 0)
        high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / (high - low), 0, 1)
        inv = extra / factor * ramp + extra * (1 - ramp)
        scale = _yarn_mscale(factor, rs.get("mscale", 1)) \
            / _yarn_mscale(factor, rs.get("mscale_all_dim", 0))
    ang = jnp.outer(jnp.arange(cfg.max_position_embeddings,
                               dtype=jnp.float32), inv)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


# ------------------------------------------------------------ pure pieces
def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _rope(x, cos, sin):
    """x (..., D) rotated as interleaved pairs; cos, sin (..., D / 2)
    broadcast against x's leading axes."""
    xr = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = xr[..., 0], xr[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _span_positions(span, s):
    """(positions (B, S), entries to write a row (B,), first position
    written (B,)) of a span, as arrays."""
    start = jnp.asarray(span.start._data, jnp.int32)
    t = jnp.arange(s, dtype=jnp.int32)[None, :]
    if span.kind == "prefill":
        first = start.reshape(1)
        return first[:, None] + t, \
            jnp.asarray(span.live._data, jnp.int32).reshape(1), first
    first = start - 1                    # through the first input token
    if span.kind == "decode":
        count = (start > 0).astype(jnp.int32)
    else:
        count = jnp.where(start > 0,
                          jnp.asarray(span.live._data, jnp.int32) + 1, 0)
    return first[:, None] + t, count, first


def _expanded_attention(q_nope, q_pe, latent, w_kvb, qpos, *, heads, d_nope,
                        d_v, rank, sm_scale, flash):
    """ONE sequence's causal attention in the expanded form: q_nope (S,
    H, d_nope), q_pe (S, H, d_rope), latent (T, >= rank + d_rope) the
    sequence's entries by position, qpos (S,) the queries' positions.
    Keys and values of every head are made from the latent
    (`kv_b_proj`); a query sees the entries at positions <= its own.
    Returns (S, H, d_v).

    flash=True (a prefill chunk) runs the Pallas flash kernel in its
    packed form, which takes the queries' and keys' positions as data and
    skips the key blocks that lie wholly in a chunk's future; the values
    are padded to the keys' width, which the kernel shares. As an XLA
    composition the float32 scores of a 2,048-token chunk over a
    5,120-entry table went through HBM several times a layer: 200 of a
    chunk step's 300 ms (PR 31, v5e). flash=False (a verify span: a few
    queries a row, under vmap) is that composition."""
    s, t = q_nope.shape[0], latent.shape[0]
    d_rope = q_pe.shape[-1]
    kv = jnp.dot(latent[:, :rank], w_kvb).reshape(t, heads, d_nope + d_v)
    k_pe = jnp.broadcast_to(latent[:, None, rank:rank + d_rope],
                            (t, heads, d_rope))
    k = jnp.concatenate([kv[..., :d_nope], k_pe], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    v = kv[..., d_nope:]
    # a padded query's position may be negative (a padded verify row):
    # clamp, so that its softmax sees one key and stays finite
    qpos = jnp.maximum(qpos, 0)
    kpos = jnp.arange(t, dtype=jnp.int32)
    if flash:
        from ..kernels.flash_attention import flash_attention_varlen_bshd
        ones = jnp.ones((1, 1), jnp.int32)
        v = jnp.pad(v, ((0, 0), (0, 0), (0, d_nope + d_rope - d_v)))
        out = flash_attention_varlen_bshd(
            q[None], k[None], v[None], jnp.broadcast_to(ones, (1, s)),
            jnp.broadcast_to(ones, (1, t)), causal=True, sm_scale=sm_scale,
            q_positions=qpos[None], kv_positions=kpos[None])
        return out[0, ..., :d_v]
    sc = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=jnp.float32)
    sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None],
                   sc * sm_scale, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def route(x, w_gate, bias, *, top_k, scale, norm):
    """Sigmoid routing over ALL experts: scores in float32, the top k of
    score + bias, weights the scores at the chosen (without the bias),
    normalised, times `scale`. x (T, H). Returns (idx (T, k) int32,
    weights (T, k) float32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_gate.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def held_experts(x, live, idx, w, e_gate, e_up, e_down, *, offset):
    """The held experts' part of an expert layer, dropless: every
    (token, expert) pair whose expert is held is computed, whatever the
    load. Pairs are sorted by expert into ragged groups on tile
    boundaries and the three products run over the groups
    (`kernels/grouped_matmul.py`); a token's part is the weighted sum of
    its pairs' rows. x (T, H); live (T,) bool; idx, w (T, k) from
    `route`; e_gate, e_up (E_held, H, I), e_down (E_held, I, H). Returns
    (y (T, H), the PAGED_COUNTERS of this layer (4,) int32)."""
    from ..kernels.grouped_matmul import (grouped_matmul, grouped_swiglu,
                                          padded_rows, ragged_layout,
                                          row_tile)
    t, k = idx.shape
    n_held = e_gate.shape[0]
    local = idx - offset
    held = (local >= 0) & (local < n_held) & live[:, None]
    group = jnp.where(held, local, n_held).reshape(t * k)
    tm = row_tile(t * k)
    rows = padded_rows(t * k, n_held, tm)
    src, slot_of, tile_group, live_tiles, sizes = ragged_layout(
        group, n_held, tm, rows)
    live_tiles = jnp.maximum(live_tiles, 1)
    # slot -> its pair's token (an empty slot reads past the end: zeros)
    xp = jnp.take(x, src // k, axis=0, mode="fill", fill_value=0)
    h = grouped_swiglu(xp, e_gate, e_up, tile_group, live_tiles, tm)
    yp = grouped_matmul(h, e_down, tile_group, live_tiles, tm)
    # pair -> its slot's row (a pair of no held expert reads zeros)
    yg = jnp.take(yp, slot_of.reshape(t, k), axis=0, mode="fill",
                  fill_value=0)
    wk = jnp.where(held, w, 0.0)
    y = jnp.einsum("tkh,tk->th", yg.astype(jnp.float32), wk)
    counts = jnp.stack([jnp.sum(live) * k, jnp.sum(held),
                        jnp.sum(sizes > 0), jnp.max(sizes)])
    return y.astype(x.dtype), counts.astype(jnp.int32)


# ------------------------------------------------------------------ layers
class _Weight(nn.Layer):
    """A bare matrix (in, out) under the published name `<x>.weight`."""

    def __init__(self, shape, std=0.02):
        super().__init__()
        self.weight = self.create_parameter(
            shape, default_initializer=Normal(0.0, std))


class KimiK2Attention(nn.Layer):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _Weight((h, cfg.q_lora_rank))
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank,
                                        epsilon=cfg.rms_norm_eps)
        self.q_b_proj = _Weight((cfg.q_lora_rank, nh * dq))
        self.kv_a_proj_with_mqa = _Weight(
            (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim))
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank,
                                         epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = _Weight(
            (cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)))
        self.o_proj = _Weight((nh * cfg.v_head_dim, h))

    def _weights(self):
        return (self.q_a_proj.weight, self.q_a_layernorm.weight,
                self.q_b_proj.weight, self.kv_a_proj_with_mqa.weight,
                self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                self.o_proj.weight)

    def _project(self, x, cos, sin, wqa, qan, wqb, wkva, kvan):
        """x (B, S, hidden); cos, sin (B, S, d_rope / 2). Returns q_nope
        (B, S, H, d_nope), q_pe (B, S, H, d_rope) roped, and the cache
        entries (B, S, rank + d_rope)."""
        cfg = self.cfg
        b, s, _ = x.shape
        nh, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim)
        eps = cfg.rms_norm_eps
        q = jnp.dot(_rms(jnp.dot(x, wqa), qan, eps), wqb)
        q = q.reshape(b, s, nh, dn + dr)
        q_pe = _rope(q[..., dn:], cos[:, :, None, :], sin[:, :, None, :])
        kv = jnp.dot(x, wkva)
        c_kv = _rms(kv[..., :cfg.kv_lora_rank], kvan, eps)
        k_pe = _rope(kv[..., cfg.kv_lora_rank:], cos, sin)
        return q[..., :dn], q_pe, jnp.concatenate([c_kv, k_pe], axis=-1)

    def chunk_path(self, s, t, dtype) -> str:
        """Which form a prefill chunk of `s` queries over `t` gathered
        entries attends in: "flash" wherever the flash kernel's tiling
        rule takes the shapes (`unsupported_reason`, the one statement of
        it), else "xla", the composition, which is several times slower
        at a long table and says so when it is chosen."""
        from ..kernels.flash_attention import unsupported_reason
        d = self.cfg.qk_nope_head_dim + self.cfg.qk_rope_head_dim
        why = unsupported_reason((1, s, 1, d), (1, t, 1, d), dtype)
        if why is None:
            return "flash"
        warnings.warn(f"a prefill chunk of {s} tokens over {t} cache entries "
                      f"attends through the XLA composition, not the flash "
                      f"kernel ({why}): pick chunk and table buckets in "
                      f"whole tiles", stacklevel=2)
        return "xla"

    def _expand(self, q_nope, q_pe, latent, wkvb, qpos, flash=False):
        cfg = self.cfg
        return _expanded_attention(
            q_nope, q_pe, latent, wkvb, qpos,
            heads=cfg.num_attention_heads, d_nope=cfg.qk_nope_head_dim,
            d_v=cfg.v_head_dim, rank=cfg.kv_lora_rank,
            sm_scale=yarn_softmax_scale(cfg), flash=flash)

    def _absorbed_query(self, q_nope, q_pe, wkvb):
        """[q_nope W_kvb^K | q_pe] (B, H, rank + d_rope), and W_kvb^V
        (H, rank, d_v) for the way back."""
        cfg = self.cfg
        nh, dn, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.v_head_dim)
        w = wkvb.reshape(cfg.kv_lora_rank, nh, dn + dv)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w[..., :dn],
                           preferred_element_type=jnp.float32)
        q_abs = jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], axis=-1)
        return q_abs, w[..., dn:]

    def forward(self, x, cos, sin):
        """Full causal attention over x (B, S, hidden), no cache: the
        expanded form, a sequence at a time."""
        def f(xx, cc, ss, wqa, qan, wqb, wkva, kvan, wkvb, wo):
            b, s, _ = xx.shape
            qn, qp, ent = self._project(
                xx, jnp.broadcast_to(cc[None], (b,) + cc.shape),
                jnp.broadcast_to(ss[None], (b,) + ss.shape),
                wqa, qan, wqb, wkva, kvan)
            pos = jnp.arange(s, dtype=jnp.int32)
            o = jax.vmap(lambda a, c, e: self._expand(a, c, e, wkvb, pos))(
                qn, qp, ent)
            return jnp.dot(o.reshape(b, s, -1), wo)
        return apply_op("mla_attention", f, x, cos, sin, *self._weights())

    def paged(self, x, cos, sin, cache, block_tables, kind, pos, count,
              first):
        """One span (of `kind`) over the paged latent cache: writes its
        entries, then attends (decode: the absorbed kernel over the
        pool; prefill and verify: the expanded form over each row's
        gathered entries). Returns (out, cache)."""
        from ..kernels.mla_attention import mla_paged_decode, mla_paged_write
        cfg = self.cfg

        def f(xx, cc, ss, pool, bt, pp, cnt, fst, wqa, qan, wqb, wkva, kvan,
              wkvb, wo):
            b, s, _ = xx.shape
            qn, qp, ent = self._project(xx, cc, ss, wqa, qan, wqb, wkva,
                                        kvan)
            bt2 = bt.reshape(b, -1)
            pool = mla_paged_write(pool, ent, bt2, cnt, fst)
            if kind == "decode":
                q_abs, w_v = self._absorbed_query(qn[:, 0], qp[:, 0], wkvb)
                q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (
                    0, pool.shape[-1] - q_abs.shape[-1])))
                o_lat = mla_paged_decode(
                    q_abs, pool, bt2, fst + 1, rank=cfg.kv_lora_rank,
                    sm_scale=yarn_softmax_scale(cfg))
                o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(xx.dtype), w_v,
                               preferred_element_type=jnp.float32)
                o = o.astype(xx.dtype).reshape(b, 1, -1)
            else:
                lat = jnp.take(pool, bt2.astype(jnp.int32), axis=0)
                lat = lat.reshape(b, -1, pool.shape[-1])
                if kind == "prefill":      # one sequence
                    o = self._expand(
                        qn[0], qp[0], lat[0], wkvb, pp[0],
                        flash=self.chunk_path(s, lat.shape[1],
                                              xx.dtype) == "flash")
                else:
                    o = jax.vmap(
                        lambda a, c, e, p: self._expand(a, c, e, wkvb, p))(
                        qn, qp, lat, pp)
                o = o.reshape(b, s, -1)
            return jnp.dot(o, wo), pool

        return apply_op("mla_paged_attention", f, x, cos, sin, cache,
                        block_tables, pos, count, first, *self._weights())


class KimiK2Experts(nn.Layer):
    """The held experts' matrices, stacked: gate_proj, up_proj
    (E_held, hidden, I), down_proj (E_held, I, hidden)."""

    def __init__(self, n, h, i):
        super().__init__()
        init = Normal(0.0, 0.02)
        self.gate_proj = self.create_parameter((n, h, i),
                                               default_initializer=init)
        self.up_proj = self.create_parameter((n, h, i),
                                             default_initializer=init)
        self.down_proj = self.create_parameter((n, i, h),
                                               default_initializer=init)


class KimiK2Gate(nn.Layer):
    def __init__(self, h, n):
        super().__init__()
        self.weight = self.create_parameter(
            (h, n), default_initializer=Normal(0.0, 0.02))
        self.e_score_correction_bias = self.create_parameter(
            (n,), default_initializer=Constant(0.0))


class KimiK2MoE(nn.Layer):
    """An expert layer's share: routing over all experts, the held
    experts' part, and the shared expert."""

    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        if not 0 <= cfg.expert_offset <= cfg.n_routed_experts - cfg.held:
            raise ValueError(
                f"experts {cfg.expert_offset}..{cfg.expert_offset + cfg.held}"
                f" are not among the {cfg.n_routed_experts} routed")
        h, i = cfg.hidden_size, cfg.moe_intermediate_size
        self.gate = KimiK2Gate(h, cfg.n_routed_experts)
        self.experts = KimiK2Experts(cfg.held, h, i)
        self.shared_experts = LlamaMLP(SimpleNamespace(
            hidden_size=h, intermediate_size=i * cfg.n_shared_experts,
            use_bias=False))

    def routed(self, x, live=None):
        """The held experts' part of x (B, S, hidden), and the layer's
        counters; `live` (B, S) bool marks the real tokens."""
        cfg = self.cfg

        def f(xx, lv, wg, bias, eg, eu, ed):
            b, s, h = xx.shape
            flat = xx.reshape(b * s, h)
            idx, w = route(flat, wg, bias, top_k=cfg.num_experts_per_tok,
                           scale=cfg.routed_scaling_factor,
                           norm=cfg.norm_topk_prob)
            y, counts = held_experts(flat, lv.reshape(b * s), idx, w, eg, eu,
                                     ed, offset=cfg.expert_offset)
            return y.reshape(b, s, h), counts

        if live is None:
            live = Tensor(jnp.ones(tuple(x.shape[:2]), bool))
        return apply_op("moe_held_experts", f, x, live, self.gate.weight,
                        self.gate.e_score_correction_bias,
                        self.experts.gate_proj, self.experts.up_proj,
                        self.experts.down_proj)

    def forward(self, x, live=None):
        y, counts = self.routed(x, live)
        return y + self.shared_experts(x), counts


class KimiK2DecoderLayer(nn.Layer):
    def __init__(self, cfg: KimiK2Config, index: int):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.self_attn = KimiK2Attention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_norm_eps)
        self.sparse = index >= cfg.first_k_dense_replace
        self.mlp = KimiK2MoE(cfg) if self.sparse else LlamaMLP(
            SimpleNamespace(hidden_size=cfg.hidden_size,
                            intermediate_size=cfg.intermediate_size,
                            use_bias=False))

    def _ffn(self, x, live):
        h = self.post_attention_layernorm(x)
        if self.sparse:
            y, counts = self.mlp(h, live)
            return x + y, counts
        return x + self.mlp(h), None

    def forward(self, x, cos, sin):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin)
        return self._ffn(x, None)[0]

    def paged(self, x, cos, sin, cache, block_tables, kind, pos, count,
              first, live):
        attn, cache = self.self_attn.paged(
            self.input_layernorm(x), cos, sin, cache, block_tables, kind,
            pos, count, first)
        x, counts = self._ffn(x + attn, live)
        return x, cache, counts


class KimiK2Model(nn.Layer):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                   cfg.hidden_size)
        self.layers = nn.LayerList([KimiK2DecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        # persistable, so that they are in `state_dict()` and reach a
        # compiled program as arguments: at 262,144 positions the two
        # tables are 67 MB, which every program would else hold as
        # constants of its own
        cos, sin = yarn_rope_tables(cfg)
        self.register_buffer("rope_cos", Tensor(cos))
        self.register_buffer("rope_sin", Tensor(sin))


class KimiK2ForCausalLM(nn.Layer):
    def __init__(self, config: KimiK2Config):
        super().__init__()
        self.cfg = config
        self.model = KimiK2Model(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=False)

    def forward(self, input_ids):
        """Logits (B, S, V) of the full causal forward, no cache.
        Inference only (the experts' grouped kernels have no gradient)."""
        from ..core.autograd import no_grad
        m = self.model
        s = input_ids.shape[1]
        with no_grad():
            cos = apply_op("rope_slice", lambda c: c[:s], m.rope_cos)
            sin = apply_op("rope_slice", lambda c: c[:s], m.rope_sin)
            x = m.embed_tokens(input_ids)
            for layer in m.layers:
                x = layer(x, cos, sin)
            return self.lm_head(m.norm(x))

    # ------------------------------------------- the engine's contract
    paged_counters = PAGED_COUNTERS

    def paged_cache_spec(self, page_size, dtype, kv_dtype=None, tp=1):
        """One layer's cache entry: ONE array (pages, page, W) in the
        served type, W = kv_lora_rank + qk_rope_head_dim in whole lane
        tiles; K and V are views of the same bytes, so a page costs them
        once."""
        from ..kernels.mla_attention import (check_supported_mla,
                                             mla_entry_width,
                                             mla_page_bytes)
        cfg, dtype = self.cfg, jnp.dtype(dtype)
        if kv_dtype is not None:
            raise ValueError("the latent cache is kept in the served type; "
                             f"kv_dtype {kv_dtype!r} is not supported")
        if tp != 1:
            raise ValueError("latent attention is replicated over a chip's "
                             "own requests; a 'model' axis is not "
                             "supported")
        width = mla_entry_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        check_supported_mla(cfg.num_attention_heads, cfg.kv_lora_rank,
                            cfg.qk_rope_head_dim, page_size, dtype)
        nbytes = mla_page_bytes(page_size, width, dtype)
        return PagedCacheSpec((((page_size, width), dtype, None),),
                              nbytes, nbytes)

    def paged_forward(self, input_ids, paged_caches, block_tables, span):
        """The one paged entry (models/paged.py `PagedSpan`): logits at
        the chunk's last live position (prefill) or at every position
        of every row, the caches, and PAGED_COUNTERS summed over the
        expert layers."""
        m = self.model
        b, s = input_ids.shape
        pos, count, first = (Tensor(a) for a in _span_positions(span, s))

        def rope_rows(c, p):
            # padded positions may run off either end of the table; clip
            # (those rows are masked or discarded)
            return jnp.take(c, jnp.clip(p, 0, c.shape[0] - 1), axis=0)

        cos = apply_op("rope_gather", rope_rows, m.rope_cos, pos)
        sin = apply_op("rope_gather", rope_rows, m.rope_sin, pos)
        # the real tokens: the experts neither compute nor count the rest
        live = apply_op(
            "span_live", lambda c: jnp.arange(s)[None, :] < c[:, None], count)
        x = m.embed_tokens(input_ids)
        caches, counts = [], jnp.zeros((len(PAGED_COUNTERS),), jnp.int32)
        for i, layer in enumerate(m.layers):
            x, cache, c = layer.paged(
                x, cos, sin, paged_caches[i][0], block_tables, span.kind,
                pos, count, first, live)
            caches.append((cache,))
            if c is not None:
                counts = counts + c._data
        x = m.norm(x)
        if span.kind == "prefill":
            x = apply_op(
                "chunk_last", lambda hh, ln: jax.lax.dynamic_slice_in_dim(
                    hh, jnp.asarray(ln, jnp.int32).reshape(()) - 1, 1,
                    axis=1), x, span.live)
        return self.lm_head(x), caches, counts
