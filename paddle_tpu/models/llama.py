"""Llama-family decoder LM — the flagship pretraining model.

Capability parity: the reference trains Llama via PaddleNLP recipes on top
of fleet hybrid parallel (SURVEY.md §3.3); this module provides the model +
hybrid-parallel training step natively.

TPU-first design:
  * weights carry GSPMD shardings over the hybrid mesh axes
    ([data, pipe, sharding, sep, model]) via the fleet.mpu layers —
    ColumnParallel/RowParallel/VocabParallel place qkv/mlp/vocab exactly as
    Megatron-TP does, and XLA inserts the ICI collectives;
  * attention runs through nn.functional.scaled_dot_product_attention
    (Pallas flash kernel when eligible);
  * sequence parallelism = Shard over the 'sep' axis on the seq dim of
    activations (Ulysses-style alltoall emitted by GSPMD at the attention
    boundary);
  * the training step is compiled end-to-end with jit (fwd+bwd+AdamW).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.mpu import (ColumnParallelLinear, RowParallelLinear,
                                     VocabParallelEmbedding, _constraint,
                                     current_mesh, mark_sharding)
from ..nn import functional as F
from ..ops import manipulation as M
from ..ops.dispatch import apply_op
from jax.sharding import PartitionSpec as P

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaForCausalLMPipe", "llama_tiny", "llama_3_8b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_bias: bool = False
    sequence_parallel: bool = False
    recompute: bool = False
    dtype: str = "float32"


def llama_tiny(**kw):
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, max_position_embeddings=128)
    cfg.update(kw)
    return LlamaConfig(**cfg)


def llama_3_8b(**kw):
    cfg = dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
               num_hidden_layers=32, num_attention_heads=32,
               num_key_value_heads=8, max_position_embeddings=8192,
               rope_theta=500000.0)
    cfg.update(kw)
    return LlamaConfig(**cfg)


def _rope_cache(head_dim, max_pos, theta, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (S, D/2)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def _split_kv_args(arrs, n_tail):
    """Unpack a paged-cache apply_op arg list: (k, v[, k_scale,
    v_scale], *tail) -> (k, v, k_scale|None, v_scale|None, tail). The
    cache tuple's arity (2 full-width / 4 quantized, ISSUE 6) is the
    only thing that varies, so every paged write/attend closure shares
    this one splitter instead of forking per dtype."""
    kc, vc = arrs[0], arrs[1]
    scales = arrs[2:len(arrs) - n_tail]
    ks, vs = scales if scales else (None, None)
    return kc, vc, ks, vs, arrs[len(arrs) - n_tail:]


def _gather_kv(cache, bt, n_kv, hd, b, scale=None, cdt=None):
    """Gather a block table's pages into the dense (b, S, KVH, D) view
    the prefill/verify attention consumes; int8 caches dequantize
    during the gather (values * per-slot scales, cast to the compute
    dtype). bt is (P,) for the single-sequence prefill path and (B, P)
    for the batched verify path — the page->token transpose is the
    same swap either way."""
    idx = bt.astype(jnp.int32)
    g = jnp.take(cache, idx, axis=0)
    if scale is not None:
        g = g.astype(jnp.float32) * jnp.take(scale, idx, axis=0)[..., None]
    g = jnp.swapaxes(g, bt.ndim, bt.ndim + 1)   # (..., page, KVH, ...)
    g = g.reshape(b, -1, n_kv, hd)
    return g.astype(cdt) if scale is not None else g


def apply_rotary(x, cos, sin):
    """x: (B, S, H, D). Rotates pairs (even, odd) — GPT-J/Llama interleaved
    convention. The pairs are addressed by VIEWING D as (D/2, 2) rather
    than stride-2 lane slices (`x[..., 0::2]`): on TPU the minor dim is
    the 128-lane axis, and strided lane gathers ran at 320 GB/s vs
    788 GB/s (near HBM roofline) for the reshape form — measured on a
    v5e at (4, 2048, 12, 128), the TRAIN path's shapes: thousands of
    activation rows; the math is bit-identical. On the serving engine's
    paged launches (64-512 rows out of a 4096-wide projection) the
    compiler moved this pair view onto the projection's WEIGHT, three
    passes over q_proj and k_proj a launch (PR 32), so the paged spans
    call `apply_rotary_paged`; this one stays the train path's."""
    xr = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1 = xr[..., 0]
    x2 = xr[..., 1]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1)
    return out.reshape(x.shape)


def apply_rotary_paged(x, cos, sin):
    """The paged spans' rotation: x (B, S, H, D), cos/sin broadcastable
    to (B, S, 1, D/2) (decode: each row's position; a chunk: each
    token's; verify: each row's span). The same interleaved (2i, 2i+1)
    rotation as `apply_rotary`, the same products and sums in the same
    dtype, so bit-identical to it: out[2i] = x[2i] c_i + x[2i+1] (-s_i),
    out[2i+1] = x[2i+1] c_i + x[2i] s_i. A lane's partner is reached by
    ROLLING the minor axis, never by viewing D as (D/2, 2): on a paged
    launch x is 64-512 rows out of a 4096-wide projection, and the
    compiler moved that view onto the projection's WEIGHT (three passes
    over q_proj and k_proj every launch, PR 32)."""
    even = np.arange(x.shape[-1]) % 2 == 0     # a constant of the program
    c = jnp.repeat(cos, 2, axis=-1)
    s = jnp.repeat(sin, 2, axis=-1)
    partner = jnp.where(even, jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return x * c + partner * jnp.where(even, -s, s)


def _lora(name, x, y):
    """Multi-LoRA serving hook (ISSUE 15): adds the active launch
    scope's per-row adapter delta to a projection output. With no
    scope active (training, lora-less serving) it returns `y`
    UNTOUCHED — the traced graph is exactly what it always was; the
    cost is one thread-local read per projection per trace."""
    from ..serving.lora.runtime import apply_lora
    return apply_lora(name, x, y)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        h = config.hidden_size
        self.head_dim = h // config.num_attention_heads
        self.n_heads = config.num_attention_heads
        self.n_kv = config.num_key_value_heads
        self.q_proj = ColumnParallelLinear(h, h, has_bias=config.use_bias,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(h, self.n_kv * self.head_dim,
                                           has_bias=config.use_bias,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(h, self.n_kv * self.head_dim,
                                           has_bias=config.use_bias,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(h, h, has_bias=config.use_bias,
                                        input_is_parallel=True)

    def forward(self, x, cos, sin, cache=None, cache_pos=None):
        b, s, _ = x.shape
        q = M.reshape(self.q_proj(x), [b, s, self.n_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.n_kv, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.n_kv, self.head_dim])
        q = apply_op("rope", apply_rotary, q, cos, sin)
        k = apply_op("rope", apply_rotary, k, cos, sin)
        if cache is not None and cache_pos is not None:
            # fixed-size cache buffers + write position: the jit-compiled
            # decode path (generate) — buffer shape never changes, so one
            # compiled program serves every step (lax.while_loop-able)
            pk, pv = cache
            pos = jnp.asarray(cache_pos, jnp.int32)

            def _write(buf, new):
                return jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype),
                    (jnp.int32(0), pos, jnp.int32(0), jnp.int32(0)))

            k = apply_op("cache_write", _write, pk, k)
            v = apply_op("cache_write", _write, pv, v)
            cache = (k, v)
            max_len = int(pk.shape[1])

            def _mask(_q):
                qpos = pos + jnp.arange(s, dtype=jnp.int32)
                kpos = jnp.arange(max_len, dtype=jnp.int32)
                return (kpos[None, :] <= qpos[:, None])[None, None]

            mask = apply_op("cache_mask", _mask, q)
        elif cache is not None:
            pk, pv = cache
            k = M.concat([pk, k], axis=1)
            v = M.concat([pv, v], axis=1)
            cache = (k, v)
            mask = None
        else:
            mask = None
        if self.n_kv != self.n_heads:
            rep = self.n_heads // self.n_kv
            k = apply_op("repeat_kv", lambda a: jnp.repeat(a, rep, axis=2), k)
            v = apply_op("repeat_kv", lambda a: jnp.repeat(a, rep, axis=2), v)
        # causal whenever we score more than one query position (prefill with
        # a cache included); single-token decode needs no mask. The sdpa
        # causal mask is key-offset-aware (tril with k=sk-sq). The fixed-
        # buffer path encodes causality + validity in its own bool mask.
        if mask is not None:
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=mask)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=(s > 1))
        out = M.reshape(out, [b, s, self.n_heads * self.head_dim])
        out = self.o_proj(out)
        return (out, cache) if cache is not None else out

    def _gathered_dense(self, kv, block_tables, b, cdt):
        """Dense (b, S, KVH, D) K/V views of a sequence's gathered pages
        (the prefill/verify read path); quantized caches dequantize
        during the gather. One implementation for both the (P,)
        single-sequence and (B, P) batched block tables."""
        n_kv, hd = self.n_kv, self.head_dim
        if len(kv) == 4:
            def _g(cache, scale, bt):
                return _gather_kv(cache, bt, n_kv, hd, b,
                                  scale=scale, cdt=cdt)
            kd = apply_op("paged_gather_dequant", _g, kv[0], kv[2],
                          block_tables)
            vd = apply_op("paged_gather_dequant", _g, kv[1], kv[3],
                          block_tables)
        else:
            def _g(cache, bt):
                return _gather_kv(cache, bt, n_kv, hd, b)
            kd = apply_op("paged_gather", _g, kv[0], block_tables)
            vd = apply_op("paged_gather", _g, kv[1], block_tables)
        mesh = current_mesh()
        if mesh is not None and mesh.shape.get("model", 1) > 1:
            # TP serving: keep the gathered dense view sharded on the
            # kv-head axis (the caches' page contents are head-sharded,
            # so the gather never needs to materialize other shards'
            # heads)
            spec = P(None, None, "model", None)
            kd = apply_op("paged_gather_shard",
                          lambda a: _constraint(a, spec), kd)
            vd = apply_op("paged_gather_shard",
                          lambda a: _constraint(a, spec), vd)
        return kd, vd

    def _paged_qk(self, q2, k2, cos, sin, expand):
        """The q and k projections' 2-D outputs (B, S, heads * D) of one
        paged span -> roped (B, S, heads, D). `expand` are the axes the
        gathered rope rows lack of (B, S, 1, D/2): (1, 2) for decode's
        (B, D/2), (0, 2) for a chunk's (S, D/2), (2,) for verify's
        (B, S, D/2).

        The barrier keeps the heads view OFF the dot: without it the
        compiler gives the dot a heads-major output and transposes the
        WEIGHT for it every launch (33.6 + 8.4 MB a layer against 0.5 MB
        of activations at 64 rows); with it any relayout falls on the
        activation (PR 32)."""
        b, s = q2.shape[:2]

        def rope(x, c, sn):
            return apply_rotary_paged(x, jnp.expand_dims(c, expand),
                                      jnp.expand_dims(sn, expand))

        def heads(y, n):
            y = apply_op("proj_out", jax.lax.optimization_barrier, y)
            y = M.reshape(y, [b, s, n, self.head_dim])
            return apply_op("rope_paged", rope, y, cos, sin)
        return heads(q2, self.n_heads), heads(k2, self.n_kv)

    def paged(self, x, cos, sin, kv, block_tables, span):
        """One span of query positions over the PAGED KV cache (the
        serving engine's path). `models/paged.py` `PagedSpan` says, for
        each kind, which positions the tokens sit at, which of them are
        live and what the block table's shape is.

        x (B, S, hidden); cos/sin the rope rows already gathered at the
        span's absolute positions — (B, D/2) decode, (S, D/2) prefill,
        (B, S, D/2) verify; kv = (k_cache, v_cache) with caches
        (num_pages, KVH, page, D), or the QUANTIZED 4-tuple (k, v,
        k_scale, v_scale) with int8 value pages and (num_pages, KVH,
        page) fp32 per-slot scales (ISSUE 6). Returns (out, kv) with the
        updated cache tuple, same arity.

        The span's roped K/V are WRITTEN first (quantize-on-write for
        int8): decode's one token at position start - 1
        (`paged_cache_write`); a chunk's first `live` tokens from
        position `start` (`paged_cache_write_range`); a verify row's
        1 + live tokens from position start - 1
        (`paged_cache_write_span`). Rewriting a position already written
        is idempotent — quantize-on-write is deterministic — so
        supervisor retries, a frozen row's scan steps and
        rollback-rewrites stay bit-identical.

        Then it ATTENDS. Decode: `kernels.paged_attention_decode` over
        the block tables (dequantize-in-kernel). Prefill and verify:
        over the GATHERED dense view of each row's pages (dequantized
        during the gather on the int8 path) — the cached prefix plus the
        span itself — under the position mask kpos <= qpos, with qpos =
        start + i for a chunk and (start - 1) + j for a verify row.
        Prefill is compute-bound, so one XLA gather per layer is the
        right capability-axis cost; a fused chunk-attention Pallas
        kernel is a perf follow-up (ROADMAP Speed 4).
        """
        from ..kernels.paged_attention import (paged_attention_decode,
                                               paged_cache_write,
                                               paged_cache_write_range,
                                               paged_cache_write_span)
        b, s, _ = x.shape
        kind = span.kind
        # the axes the gathered rope rows lack of (B, S, 1, D/2)
        expand = {"decode": (1, 2), "prefill": (0, 2), "verify": (2,)}[kind]
        q, k = self._paged_qk(_lora("q_proj", x, self.q_proj(x)),
                              _lora("k_proj", x, self.k_proj(x)),
                              cos, sin, expand)
        v = M.reshape(_lora("v_proj", x, self.v_proj(x)),
                      [b, s, self.n_kv, self.head_dim])

        where = (span.start,) if kind == "decode" else (span.start, span.live)

        def _write(*arrs):
            kc, vc, ks, vs, (kn, vn, bt, start, *live) = _split_kv_args(
                arrs, 3 + len(where))
            scales = dict(k_scale=ks, v_scale=vs)
            if kind == "decode":
                return paged_cache_write(
                    kc, vc, kn[:, 0], vn[:, 0], bt,
                    start.astype(jnp.int32) - 1, **scales)
            if kind == "prefill":
                return paged_cache_write_range(kc, vc, kn[0], vn[0], bt,
                                               live[0], start, **scales)
            return paged_cache_write_span(
                kc, vc, kn, vn, bt,
                live[0].astype(jnp.int32) + 1,       # live span tokens
                start.astype(jnp.int32) - 1,         # first token's slot
                **scales)

        kv = apply_op({"decode": "paged_cache_write",
                       "prefill": "paged_cache_write_range",
                       "verify": "paged_cache_write_span"}[kind],
                      _write, *kv, k, v, block_tables, *where)
        if kind == "decode":
            def _attend(qq, *arrs):
                kc, vc, ks, vs, (bt, sl) = _split_kv_args(arrs, 2)
                mesh = current_mesh()
                if mesh is not None and mesh.shape.get("model", 1) > 1:
                    # TP serving (ISSUE 8): heads/KV pages sharded over
                    # 'model' — each shard attends its own head slice
                    from ..kernels.paged_attention import \
                        paged_attention_decode_tp
                    return paged_attention_decode_tp(
                        qq.reshape(b, self.n_heads, self.head_dim), kc, vc,
                        bt, sl, mesh, k_scale=ks, v_scale=vs)
                return paged_attention_decode(
                    qq.reshape(b, self.n_heads, self.head_dim), kc, vc,
                    bt, sl, k_scale=ks, v_scale=vs)

            out = apply_op("paged_attention_decode", _attend, q, *kv,
                           block_tables, span.start)
        else:
            kd, vd = self._gathered_dense(kv, block_tables, b, q._data.dtype)
            if self.n_kv != self.n_heads:
                rep = self.n_heads // self.n_kv
                kd = apply_op("repeat_kv",
                              lambda a: jnp.repeat(a, rep, axis=2), kd)
                vd = apply_op("repeat_kv",
                              lambda a: jnp.repeat(a, rep, axis=2), vd)
            sk = int(kd.shape[1])

            def _mask(start):
                if kind == "prefill":
                    qpos = jnp.asarray(start, jnp.int32) \
                        + jnp.arange(s, dtype=jnp.int32)
                    kpos = jnp.arange(sk, dtype=jnp.int32)
                    return (kpos[None, :] <= qpos[:, None])[None, None]
                # padded batch rows carry start 0 -> qpos would be -1 and
                # fully mask their first row (NaN softmax); clamp to 0 so
                # dead rows stay finite — their outputs are discarded
                qpos = jnp.maximum(
                    start.astype(jnp.int32)[:, None] - 1
                    + jnp.arange(s, dtype=jnp.int32)[None, :], 0)   # (B, S)
                kpos = jnp.arange(sk, dtype=jnp.int32)
                return (kpos[None, None, :] <= qpos[:, :, None])[:, None]

            mask = apply_op("chunk_mask" if kind == "prefill"
                            else "verify_mask", _mask, span.start)
            out = F.scaled_dot_product_attention(q, kd, vd, attn_mask=mask)
        out = M.reshape(out, [b, s, self.n_heads * self.head_dim])
        return _lora("o_proj", out, self.o_proj(out)), kv


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, i, has_bias=config.use_bias,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(h, i, has_bias=config.use_bias,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(i, h, has_bias=config.use_bias,
                                           input_is_parallel=True)

    def forward(self, x):
        h = F.swiglu(_lora("gate_proj", x, self.gate_proj(x)),
                     _lora("up_proj", x, self.up_proj(x)))
        return _lora("down_proj", h, self.down_proj(h))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cos, sin, cache=None, cache_pos=None):
        h = self.input_layernorm(x)
        if cache is not None:
            attn, cache = self.self_attn(h, cos, sin, cache, cache_pos)
        else:
            attn = self.self_attn(h, cos, sin)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        return (x, cache) if cache is not None else x

    def paged(self, x, cos, sin, kv, block_tables, span):
        h = self.input_layernorm(x)
        attn, kv = self.self_attn.paged(h, cos, sin, kv, block_tables, span)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, kv


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = _rope_cache(head_dim, config.max_position_embeddings,
                               config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, caches=None, cache_pos=None):
        s = input_ids.shape[1]
        if cache_pos is not None:
            past = jnp.asarray(cache_pos, jnp.int32)
        else:
            past = caches[0][0].shape[1] if caches is not None else 0
        cos = apply_op("rope_slice",
                       lambda c: jax.lax.dynamic_slice_in_dim(c, past, s, 0),
                       self.rope_cos)
        sin = apply_op("rope_slice",
                       lambda c: jax.lax.dynamic_slice_in_dim(c, past, s, 0),
                       self.rope_sin)
        x = self.embed_tokens(input_ids)
        if self.cfg.sequence_parallel:
            x = apply_op("sp_shard",
                         lambda a: _constraint(a, P("data", "sep", None)), x)
        new_caches = []
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, cos, sin, caches[i], cache_pos)
                new_caches.append(c)
            elif self.cfg.recompute:
                x = _recompute_layer(layer, x, cos, sin)
            else:
                x = layer(x, cos, sin)
        x = self.norm(x)
        return (x, new_caches) if caches is not None else x

    def paged(self, input_ids, paged_caches, block_tables, span):
        """One span over per-layer paged KV caches (`PagedSpan`):
        input_ids (B, S); paged_caches a list of per-layer cache tuples
        — (k_cache, v_cache), or (k, v, k_scale, v_scale) for int8 KV
        (ISSUE 6). The rope rows are gathered at the span's positions:
        start - 1 a decode row, start + i a chunk, (start - 1) + j a
        verify row. Chunked prefill and radix prefix-cache hits are the
        same program: a hit just starts at start = matched tokens.
        Returns (hidden (B, S, H), new_caches) with the same tuple
        arity."""
        kind, s = span.kind, input_ids.shape[1]

        def _gather_rope(c, start):
            if kind == "decode":
                return jnp.take(c, start.astype(jnp.int32) - 1, axis=0)
            if kind == "prefill":
                pos = jnp.asarray(start, jnp.int32) \
                    + jnp.arange(s, dtype=jnp.int32)
            else:
                pos = (start.astype(jnp.int32)[:, None] - 1
                       + jnp.arange(s, dtype=jnp.int32)[None, :])    # (B, S)
            # a chunk's padded tail, a padded row (start 0) and a padded
            # span tail may run off the table; clip — those rows are
            # masked out of the attention or discarded
            return jnp.take(c, jnp.clip(pos, 0, c.shape[0] - 1), axis=0)

        cos = apply_op("rope_gather", _gather_rope, self.rope_cos,
                       span.start)
        sin = apply_op("rope_gather", _gather_rope, self.rope_sin,
                       span.start)
        x = self.embed_tokens(input_ids)
        new_caches = []
        for i, layer in enumerate(self.layers):
            x, kv = layer.paged(x, cos, sin, paged_caches[i], block_tables,
                                span)
            new_caches.append(kv)
        return self.norm(x), new_caches


def _recompute_layer(layer, x, cos, sin):
    """Activation checkpointing via jax.checkpoint over the layer's pure fn
    (parity: fleet/recompute/recompute.py RecomputeFunction)."""
    from ..jit.api import functional_call
    from ..kernels.flash_attention import _interpret_mode
    from ..nn.functional.flash_attention import sdp_kernel
    sd = layer.state_dict()
    keys = list(sd)
    # interpret-mode pallas calls can't be replayed by remat; real TPU keeps
    # the flash kernel inside the checkpointed region.
    use_flash = not _interpret_mode()

    def pure(params, xx, cc, ss):
        with sdp_kernel(enable_flash=use_flash):
            return functional_call(layer, dict(zip(keys, params)),
                                   Tensor(xx), Tensor(cc), Tensor(ss))._data

    ck = jax.checkpoint(pure, static_argnums=())
    return apply_op("recompute_layer",
                    lambda *arrs: ck(list(arrs[:len(keys)]), *arrs[len(keys):]),
                    *[sd[k] for k in keys], x, cos, sin)


def _head_and_loss(h, labels, lm_head, tied_weight):
    """LM head + shifted masked-mean cross entropy (shared by the plain and
    pipelined causal-LM heads)."""
    if lm_head is None:
        logits = apply_op("tied_head", lambda a, ww: a @ ww.T, h, tied_weight)
    else:
        logits = lm_head(h)
    if labels is None:
        return logits
    from ..distributed.fleet.mpu import ParallelCrossEntropy
    # next-token objective: logits[:, :-1] predict labels[:, 1:]
    shift_logits = apply_op("shift", lambda a: a[:, :-1, :], logits)
    shift_labels = apply_op("shift", lambda a: a[:, 1:], labels)
    loss_t = ParallelCrossEntropy()(shift_logits, shift_labels)

    # masked mean over valid (non-ignore_index) positions
    def _masked_mean(l, lab):
        valid = (lab != -100).astype(l.dtype)
        return jnp.sum(l[..., 0] * valid) / jnp.maximum(jnp.sum(valid), 1.0)

    return apply_op("masked_mean", _masked_mean, loss_t, shift_labels)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)

    def forward(self, input_ids, labels=None, caches=None, cache_pos=None):
        if caches is not None:
            h, caches = self.model(input_ids, caches, cache_pos)
        else:
            h = self.model(input_ids)
        tied = self.model.embed_tokens.weight if self.lm_head is None else None
        out = _head_and_loss(h, labels, self.lm_head, tied)
        if labels is not None:
            return out
        return (out, caches) if caches is not None else out

    # ------------------------------------------- the engine's contract
    # (models/paged.py): the cache entry, the one paged entry over a
    # span of query positions, and no counters.
    paged_counters = ()

    def paged_cache_spec(self, page_size, dtype, kv_dtype=None, tp=1):
        """One layer's cache entry: K pages and V pages of
        `(pages, KVH, page, D)` in the served type, or int8 with their
        fp32 per-slot scale pages `(pages, KVH, page)`; head-sharded over
        'model' under tensor parallelism (page IDS stay global). Raises
        at construction, not at the first decode launch, where the Pallas
        kernel's static constraints refuse the PER-SHARD geometry."""
        from ..kernels.paged_attention import (KV_SCALE_DTYPE,
                                               check_supported_paged,
                                               paged_page_bytes)
        cfg, dtype = self.cfg, jnp.dtype(dtype)
        kvh, heads = cfg.num_key_value_heads, cfg.num_attention_heads
        hd = cfg.hidden_size // heads
        if tp > 1 and (kvh % tp or heads % tp):
            which, n = (("num_key_value_heads", kvh) if kvh % tp
                        else ("num_attention_heads", heads))
            raise ValueError(f"{which} {n} not divisible by model-axis "
                             f"degree {tp}")
        check_supported_paged((1, heads // tp, hd),
                              (1, kvh // tp, page_size, hd),
                              dtype, kv_dtype=kv_dtype)
        name = kv_dtype if kv_dtype is not None else str(dtype)
        shape = (kvh, page_size, hd)
        kv_spec = P(None, "model", None, None) if tp > 1 else None
        cache_dtype = jnp.int8 if kv_dtype == "int8" else dtype
        entries = [(shape, cache_dtype, kv_spec)] * 2
        if kv_dtype == "int8":
            sc_spec = P(None, "model", None) if tp > 1 else None
            entries += [(shape[:2], KV_SCALE_DTYPE, sc_spec)] * 2
        from .paged import PagedCacheSpec
        return PagedCacheSpec(
            tuple(entries), paged_page_bytes(kvh, page_size, hd, name),
            paged_page_bytes(kvh // tp, page_size, hd, name))

    def paged_forward(self, input_ids, paged_caches, block_tables, span):
        """The one paged entry (models/paged.py `PagedSpan`): the
        paged-KV transformer and the LM head; no counters.

        A prefill span's head runs at the chunk's LAST LIVE position
        only — the sole row serving consumes (and only on the final
        chunk at that); a full (S, V) head would spend ~S x the head
        FLOPs per chunk for nothing: logits (1, 1, V). Decode and verify
        spans head EVERY position, (B, 1, V) and (B, S, V): the verify
        consumer needs logits after each draft token (position j's
        logits score draft j+1 and supply the correction/bonus token),
        so there the full head is the point, not waste (S = K+1 is
        small)."""
        if span.kind not in ("decode", "prefill", "verify"):
            raise ValueError(f"unknown span kind {span.kind!r}")
        h, caches = self.model.paged(input_ids, paged_caches, block_tables,
                                     span)
        if span.kind == "prefill":
            def _last(hh, ln):
                return jax.lax.dynamic_slice_in_dim(
                    hh, jnp.asarray(ln, jnp.int32) - 1, 1, axis=1)

            h = apply_op("chunk_last", _last, h, span.live)
        tied = self.model.embed_tokens.weight if self.lm_head is None else None
        return _head_and_loss(h, None, self.lm_head, tied), caches, ()

    # -------------------------------------------------------- generation
    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, top_p=1.0, eos_token_id=None, use_jit=False,
                 seed=None):
        """Greedy/sampled decode with KV cache.

        use_jit=True compiles prefill + the full decode loop + sampling
        into ONE XLA program over a fixed-size cache
        (models/generation.py jit_generate — the TPU-native serving
        path); the default eager loop re-dispatches per step."""
        if use_jit:
            from .generation import jit_generate
            return jit_generate(self, input_ids,
                                max_new_tokens=max_new_tokens,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p, eos_token_id=eos_token_id,
                                seed=seed)
        from ..core.autograd import no_grad
        from ..framework.random import rng_key
        from .generation import _sample_arr
        with no_grad():
            b, s = input_ids.shape
            key = (jax.random.PRNGKey(seed) if seed is not None
                   else rng_key())
            caches = [(Tensor(jnp.zeros((b, 0, l.self_attn.n_kv,
                                         l.self_attn.head_dim), jnp.float32)),
                       Tensor(jnp.zeros((b, 0, l.self_attn.n_kv,
                                         l.self_attn.head_dim), jnp.float32)))
                      for l in self.model.layers]
            logits, caches = self.forward(input_ids, caches=caches)
            out_ids = [input_ids]
            import numpy as _np
            done = _np.zeros((b,), bool)
            for _ in range(max_new_tokens):
                last = logits._data[:, -1, :]  # stays on device
                key, kn = jax.random.split(key)
                nxt_arr = _sample_arr(last, kn, float(temperature),
                                      int(top_k), float(top_p))
                if eos_token_id is not None:
                    nxt_arr = jnp.where(jnp.asarray(done),
                                        jnp.int32(eos_token_id), nxt_arr)
                    done = _np.asarray(
                        jnp.logical_or(jnp.asarray(done),
                                       nxt_arr == eos_token_id))
                nxt = Tensor(nxt_arr.astype(input_ids._data.dtype)[:, None])
                out_ids.append(nxt)
                if eos_token_id is not None and done.all():
                    pad = Tensor(jnp.full(
                        (b, max_new_tokens - len(out_ids) + 1),
                        eos_token_id, input_ids._data.dtype))
                    if pad.shape[1] > 0:
                        out_ids.append(pad)
                    break
                logits, caches = self.forward(nxt, caches=caches)
            return M.concat(out_ids, axis=1)


# ------------------------------------------------------------------ pipeline
class _LlamaStage(nn.Layer):
    """One pipeline chunk: `n_layers` consecutive decoder layers."""

    def __init__(self, config: LlamaConfig, n_layers: int):
        super().__init__()
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(n_layers)])

    def forward(self, x, cos, sin):
        for layer in self.layers:
            x = layer(x, cos, sin)
        return x


class LlamaForCausalLMPipe(nn.Layer):
    """Pipeline-parallel Llama with decoder chunks stacked over 'pipe'.

    Parity: the reference expresses pipelined models as a `PipelineLayer`
    of LayerDescs segmented across stages and scheduled by
    `PipelineParallel.forward_backward_pipeline` (1F1B,
    `fleet/meta_parallel/pipeline_parallel.py:565`) or
    `PipelineParallelWithInterleave` (`:1161`), moving activations with
    NCCL p2p per micro-step.

    TPU-native: embedding / final norm / LM head are replicated over the
    pipe axis (sharded over model/data as usual); the homogeneous decoder
    stack is partitioned into `num_stages * n_virtual` chunks whose
    parameters are stacked into (n_virtual, num_stages, ...) arrays sharded
    over 'pipe', and the whole micro-batch schedule runs as one compiled
    lax.scan with ppermute edges (distributed.pipeline.pipeline_forward).
    jax AD derives the reverse pipeline; jax.checkpoint bounds activation
    memory the way 1F1B does. TP composes: the shard_map is manual only on
    'pipe', so GSPMD still shards the mpu layers inside each stage.
    """

    def __init__(self, config: LlamaConfig, num_stages: int = 2,
                 num_microbatches: int = 2, n_virtual: int = 1):
        super().__init__()
        self.cfg = config
        self.num_stages = int(num_stages)
        self.num_microbatches = int(num_microbatches)
        self.n_virtual = int(n_virtual)
        n_chunks = self.num_stages * self.n_virtual
        if config.num_hidden_layers % n_chunks != 0:
            raise ValueError(
                f"num_hidden_layers ({config.num_hidden_layers}) must divide "
                f"into num_stages*n_virtual ({n_chunks}) chunks")
        self.layers_per_chunk = config.num_hidden_layers // n_chunks

        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = _rope_cache(head_dim, config.max_position_embeddings,
                               config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

        # Stage template (held out of sublayer registration: its params are
        # placeholders rebound functionally with per-chunk slices).
        tmpl = _LlamaStage(config, self.layers_per_chunk)
        self._tmpl = [tmpl]
        tmpl_sd = tmpl.state_dict()
        self._stage_keys = list(tmpl_sd.keys())

        # Build each chunk with its own init randomness and stack:
        # leaf -> (n_virtual, num_stages, *shape), sharded P(None,'pipe',...)
        stacks = {k: [] for k in self._stage_keys}
        for _ in range(n_chunks):
            blk = _LlamaStage(config, self.layers_per_chunk)
            sd = blk.state_dict()
            for k in self._stage_keys:
                stacks[k].append(sd[k]._data)
        for k in self._stage_keys:
            arr = jnp.stack(stacks[k], axis=0)
            arr = arr.reshape(self.n_virtual, self.num_stages, *arr.shape[1:])
            p = Tensor(arr, stop_gradient=False)
            p._is_param = True
            base_spec = getattr(tmpl_sd[k], "_spec", None)
            tail = tuple(base_spec) if base_spec is not None else \
                tuple([None] * (arr.ndim - 2))
            self.add_parameter(self._pname(k), p)
            mark_sharding(p, P(None, "pipe", *tail))

    @staticmethod
    def _pname(key):
        return "pipe_stages__" + key.replace(".", "__")

    @classmethod
    def from_causal_lm(cls, model: "LlamaForCausalLM", num_stages: int = 2,
                       num_microbatches: int = 2, n_virtual: int = 1):
        """Build a pipelined model carrying `model`'s weights (chunk c holds
        decoder layers [c*L/C, (c+1)*L/C) at ring pass c // num_stages,
        device c % num_stages)."""
        pipe = cls(model.cfg, num_stages=num_stages,
                   num_microbatches=num_microbatches, n_virtual=n_virtual)
        pipe.embed_tokens.weight.set_value(model.model.embed_tokens.weight)
        pipe.norm.weight.set_value(model.model.norm.weight)
        if pipe.lm_head is not None:
            pipe.lm_head.weight.set_value(model.lm_head.weight)
        plain_sd = model.state_dict()
        n_chunks = pipe.num_stages * pipe.n_virtual
        for k in pipe._stage_keys:
            # template key: "layers.<j>.<suffix>"
            _, j, suffix = k.split(".", 2)
            leaf = pipe._parameters[pipe._pname(k)]
            arr = leaf._data
            for c in range(n_chunks):
                i = c * pipe.layers_per_chunk + int(j)
                v, d = divmod(c, pipe.num_stages)
                src = plain_sd[f"model.layers.{i}.{suffix}"]._data
                arr = arr.at[v, d].set(src.astype(arr.dtype))
            leaf._data = arr
        return pipe

    def forward(self, input_ids, labels=None):
        from ..distributed.fleet.mpu import current_mesh
        from ..distributed.pipeline import pipeline_forward
        from ..jit.api import functional_call
        from ..kernels.flash_attention import _interpret_mode
        from ..nn.functional.flash_attention import sdp_kernel

        cfg = self.cfg
        b, s = input_ids.shape
        cos = apply_op("rope_slice", lambda c: c[:s], self.rope_cos)
        sin = apply_op("rope_slice", lambda c: c[:s], self.rope_sin)
        x = self.embed_tokens(input_ids)
        if cfg.sequence_parallel:
            x = apply_op("sp_shard",
                         lambda a: _constraint(a, P("data", "sep", None)), x)

        tmpl = self._tmpl[0]
        keys = self._stage_keys
        leaves = [self._parameters[self._pname(k)] for k in keys]
        mesh = current_mesh()
        use_pipe = (mesh is not None and "pipe" in mesh.shape
                    and mesh.shape["pipe"] == self.num_stages
                    and self.num_stages > 1)
        # interpret-mode pallas calls can't be replayed by remat; real TPU
        # keeps the flash kernel inside the checkpointed stage.
        use_flash = not _interpret_mode()

        def stage_raw(params, xx, cc, ss):
            with sdp_kernel(enable_flash=use_flash):
                return functional_call(tmpl, {k: v for k, v in params.items()},
                                       Tensor(xx), Tensor(cc), Tensor(ss))._data

        if use_pipe:
            n_micro = self.num_microbatches
            if b % n_micro != 0:
                raise ValueError(f"batch {b} not divisible by "
                                 f"num_microbatches {n_micro}")

            def pipe_raw(*arrs):
                pl, (xx, cc, ss) = arrs[:len(keys)], arrs[len(keys):]
                params = dict(zip(keys, pl))
                if self.n_virtual == 1:
                    params = {k: a[0] for k, a in params.items()}
                micro = xx.reshape(n_micro, b // n_micro, *xx.shape[1:])
                out = pipeline_forward(
                    params, micro,
                    lambda p, xm, cc_, ss_: stage_raw(p, xm, cc_, ss_),
                    mesh, extras=(cc, ss), n_virtual=self.n_virtual,
                    remat=True)
                return out.reshape(b, *out.shape[2:])

            x = apply_op("llama_pipeline", pipe_raw, *leaves, x, cos, sin)
        else:
            # No live pipe mesh: run chunks sequentially (same math).
            def seq_raw(*arrs):
                pl, (xx, cc, ss) = arrs[:len(keys)], arrs[len(keys):]
                y = xx
                for v in range(self.n_virtual):
                    for d in range(self.num_stages):
                        pv = {k: a[v, d] for k, a in zip(keys, pl)}
                        y = stage_raw(pv, y, cc, ss)
                return y

            x = apply_op("llama_pipeline_seq", seq_raw, *leaves, x, cos, sin)

        x = self.norm(x)
        tied = self.embed_tokens.weight if self.lm_head is None else None
        return _head_and_loss(x, labels, self.lm_head, tied)
