"""Cohere2-MoE (`model_type` cohere2_moe; Command A+): window and full
attention layers interleaved over a paged K/V cache that knows its layer
groups, a PARALLEL attention + expert block under one LayerNorm, and
sigmoid-routed experts beside averaged shared experts, served as one
chip's SHARE of an expert-parallel deployment.

Capability parity: the reference serves grouped-query and mixture-of-
experts families through its fused kernel packs
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention.cu`, whose
`max_dec_len_this_time` / window arguments bound what a step reads, and
`python/paddle/incubate/nn/functional/` moe dispatch / ffn / reduce);
rebuilt here over the engine's paged contract (`models/paged.py`).

One layer, as published (`Cohere2MoeConfig` carries the config.json keys):

  norm       h = LayerNorm(x): mean subtracted, divided by sqrt(var +
             layer_norm_eps), times a weight, no bias, statistics in
             float32. ONE norm a layer (`use_parallel_block`).
  attention  on h: q = h W_q (heads x head_dim), k = h W_k, v = h W_v
             (KV heads x head_dim), no bias, no q/k norm. `layer_types`
             says which layers are "sliding_attention": RoPE over the
             whole head, interleaved pairs (`rope_gptj`), and query i
             sees key j iff i - sliding_window < j <= i. A
             "full_attention" layer has NO position encoding and is
             causal over everything. Scores x head_dim^-0.5, softmax in
             float32, o = concat(P v) W_o.
  cache      K and V pages of (pages, KVH, page, D), Llama's entry, in
             TWO layer groups (`paged_cache_spec`): the full layers' is
             unbounded, the window layers' gives a row's pages back as
             the row advances. A decode step attends through
             `kernels/paged_attention.py` with the layer's window; a
             prefill chunk through `flash_attention_chunk_gqa` over the
             window's pages only (window + chunk keys) in a window layer
             and over the context's pages in a full one.
  experts    on the SAME h: s = sigmoid(h W_g) in float32 over all
             num_experts; the top k by s; weights s at the chosen,
             divided by their sum (`norm_topk_prob`); no bias on the
             scores, no scaling factor. routed = sum_i w_i E_i(h), E a
             SwiGLU MLP `intermediate_size` wide. shared = the MEAN of
             num_shared_experts expert-shaped MLPs
             (`shared_expert_combination_strategy` "average"), kept as
             ONE MLP num_shared_experts x as wide whose output is
             divided by their number: the same sum. No token is dropped.
  block      x_out = x + attention(h) + routed + shared. After the last
             layer a final LayerNorm; logits = norm(x) E^T x logit_scale
             (the embedding, tied).
  the share  as `models/kimi_k2.py`: the expert layer is told which
             `experts_held` contiguous experts from `expert_offset` it
             holds, routes over ALL, adds its own experts' part and the
             shared experts; the partial sum goes on. No code stands in
             for the absent chips or their exchange.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.mpu import VocabParallelEmbedding
from ..nn.initializer import Constant
from ..ops.dispatch import apply_op
from .kimi_k2 import (PAGED_COUNTERS as MOE_COUNTERS, KimiK2Experts, _Weight,
                      _span_positions, held_experts, route)
from .llama import LlamaMLP, apply_rotary_paged
from .paged import PagedCacheSpec

__all__ = ["Cohere2MoeConfig", "Cohere2MoeForCausalLM", "cohere2_moe_tiny",
           "PAGED_COUNTERS", "layer_norm"]

SLIDING, FULL = "sliding_attention", "full_attention"

# the expert layers' four counts (`models/kimi_k2.py`) and what the
# attention layers had to see, whatever implements them. Over a DECODE
# span, the keys: a row's context in a full layer, at most the window in a
# window layer. Over a PREFILL chunk, the (query, key) pairs: a live
# query at position p sees p + 1 keys in a full layer and at most the
# window in a window layer. Each is 0 in the other kind of span.
PAGED_COUNTERS = MOE_COUNTERS + ("attn_decode_keys", "attn_chunk_pairs")


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # ONE expert's width
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    # every layer's kind; None: `layer_switch` - 1 window layers, then a
    # full one, repeated (`order_of_interleaved_layers` local first)
    layer_types: Optional[Tuple[str, ...]] = None
    layer_switch: int = 4
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    # the share: how many routed experts THIS chip holds, from which
    # (None: all of them, the uncut layer)
    experts_held: Optional[int] = None
    expert_offset: int = 0

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if (i + 1) % self.layer_switch == 0 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(set(self.layer_types))}; the model has "
                f"{self.num_hidden_layers} of {SLIDING} | {FULL}")

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else int(self.experts_held))

    def window_of(self, layer: int) -> Optional[int]:
        return (int(self.sliding_window)
                if self.layer_types[layer] == SLIDING else None)

    @property
    def layer_groups(self) -> Optional[Tuple[int, ...]]:
        """Each layer's cache group, 0 the full layers' and 1 the window
        layers'; None for a model without window layers (one group)."""
        if SLIDING not in self.layer_types:
            return None
        return tuple(int(t == SLIDING) for t in self.layer_types)


def cohere2_moe_tiny(**kw):
    """The same layer at toy widths (tests; widths the kernels accept)."""
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
               num_hidden_layers=4, num_attention_heads=8,
               num_key_value_heads=2, head_dim=64, num_experts=16,
               num_experts_per_tok=4, num_shared_experts=4,
               sliding_window=32, max_position_embeddings=512)
    cfg.update(kw)
    return Cohere2MoeConfig(**cfg)


# ------------------------------------------------------------ pure pieces
def layer_norm(x, w, eps):
    """(x - mean) / sqrt(var + eps) x w, no bias; statistics in float32."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope_rows(pos, head_dim, theta):
    """cos, sin (..., head_dim / 2) in float32 at integer positions `pos`,
    computed where they are used: a table over max_position_embeddings
    would be 100 MB of state that every launch reads rows of."""
    inv = 1.0 / (float(theta) ** (
        np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    ang = jnp.maximum(pos, 0).astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _dense_attention(q, k, v, qpos, kpos, sm_scale, window):
    """q (S, H, D) against k, v (T, KVH, D) as an XLA composition, a
    float32 score matrix of the whole sequence: the UNCACHED forward's
    attention (tests, a few hundred positions). The paged path never
    takes it."""
    g = q.shape[1] // k.shape[1]
    qg = q.reshape(q.shape[0], k.shape[1], g, q.shape[2])
    sc = jnp.einsum("sngd,tnd->ngst", qg, k,
                    preferred_element_type=jnp.float32) * sm_scale
    seen = kpos[None, :] <= qpos[:, None]
    if window is not None:
        seen = seen & (kpos[None, :] > qpos[:, None] - window)
    p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("ngst,tnd->sngd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(q.shape).astype(q.dtype)


# ------------------------------------------------------------------ layers
class Cohere2LayerNorm(nn.Layer):
    def __init__(self, width, eps):
        super().__init__()
        self.eps = float(eps)
        self.weight = self.create_parameter(
            (width,), default_initializer=Constant(1.0))

    def forward(self, x):
        return apply_op("cohere_layer_norm",
                        lambda a, w: layer_norm(a, w, self.eps), x,
                        self.weight)


class Cohere2MoeAttention(nn.Layer):
    """One attention layer; `window` None marks a full layer (no position
    encoding), an int a window layer (RoPE, the last `window` keys)."""

    def __init__(self, cfg: Cohere2MoeConfig, window: Optional[int]):
        super().__init__()
        self.cfg, self.window = cfg, window
        h, nh, nkv, d = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        if nh % nkv:
            raise ValueError(f"{nh} heads are not whole groups of {nkv} KV "
                             f"heads")
        self.q_proj = _Weight((h, nh * d))
        self.k_proj = _Weight((h, nkv * d))
        self.v_proj = _Weight((h, nkv * d))
        self.o_proj = _Weight((nh * d, h))

    def _weights(self):
        return (self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                self.o_proj.weight)

    def _qkv(self, x, pos, wq, wk, wv):
        """x (B, S, hidden) at positions pos (B, S) -> q (B, S, H, D), k
        and v (B, S, KVH, D), q and k roped in a window layer. The barrier
        keeps the heads view and the rotation OFF the projections'
        weights (`models/llama.py` `_paged_qk`, PR 32)."""
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.head_dim
        q, k = jax.lax.optimization_barrier((jnp.dot(x, wq), jnp.dot(x, wk)))
        q = q.reshape(b, s, cfg.num_attention_heads, d)
        k = k.reshape(b, s, cfg.num_key_value_heads, d)
        v = jnp.dot(x, wv).reshape(b, s, cfg.num_key_value_heads, d)
        if self.window is not None:
            cos, sin = _rope_rows(pos, d, cfg.rope_theta)
            cos, sin = cos[:, :, None, :], sin[:, :, None, :]
            q = apply_rotary_paged(q, cos, sin).astype(x.dtype)
            k = apply_rotary_paged(k, cos, sin).astype(x.dtype)
        return q, k, v

    def forward(self, x):
        """Causal (windowed in a window layer) attention over x (B, S,
        hidden), no cache."""
        scale = self.cfg.head_dim ** -0.5

        def f(xx, wq, wk, wv, wo):
            b, s, _ = xx.shape
            pos = jnp.arange(s, dtype=jnp.int32)
            q, k, v = self._qkv(xx, jnp.broadcast_to(pos, (b, s)), wq, wk, wv)
            o = jax.vmap(lambda a, c, e: _dense_attention(
                a, c, e, pos, pos, scale, self.window))(q, k, v)
            return jnp.dot(o.reshape(b, s, -1), wo)
        return apply_op("cohere_attention", f, x, *self._weights())

    def _chunk_keys(self, s, table_pages, page_size):
        """Pages a prefill chunk of `s` tokens gathers from a table of
        `table_pages`: the whole table in a full layer; in a window layer
        the window's and the chunk's, one more for where the chunk starts
        inside a page, rounded to eight pages (whole lane tiles of keys)
        and to the table."""
        if self.window is None:
            return table_pages
        need = -(-(self.window + s) // page_size) + 1
        return min(table_pages, -(-need // 8) * 8)

    def paged(self, x, cache, block_tables, group, kind, pos, count, first):
        """One span (of `kind`) over the paged K/V cache of this layer's
        group: writes the span's K/V, then attends (decode: the paged
        kernel with the layer's window; prefill: the flash kernel over
        the gathered pages). `block_tables` holds a table a group where
        the model has more than one (`models/paged.py`). Returns (out,
        (k_cache, v_cache))."""
        from ..kernels.flash_attention import flash_attention_chunk_gqa
        from ..kernels.paged_attention import (paged_attention_decode,
                                               paged_cache_write,
                                               paged_cache_write_range)
        cfg, window = self.cfg, self.window
        scale = cfg.head_dim ** -0.5

        def f(xx, kc, vc, bts, pp, cnt, fst, wq, wk, wv, wo):
            b, s, _ = xx.shape
            bt = bts if group is None else bts[group]
            q, k, v = self._qkv(xx, pp, wq, wk, wv)
            if kind == "decode":
                kc, vc = paged_cache_write(kc, vc, k[:, 0], v[:, 0], bt, fst)
                o = paged_attention_decode(q[:, 0], kc, vc, bt, fst + 1,
                                           sm_scale=scale, window=window)
                return jnp.dot(o.reshape(b, 1, -1), wo), kc, vc
            # a prefill chunk: one sequence
            kc, vc = paged_cache_write_range(kc, vc, k[0], v[0], bt, cnt[0],
                                             fst[0])
            ps, table = kc.shape[2], bt.shape[0]
            n = self._chunk_keys(s, table, ps)
            lo = jnp.int32(0) if n == table else jnp.clip(
                jax.lax.div(fst[0] - window, jnp.int32(ps)), 0, table - n)
            pages = jax.lax.dynamic_slice_in_dim(bt.astype(jnp.int32), lo, n)

            def keys(pool):       # (n, KVH, page, D) -> (n x page, KVH, D)
                got = jnp.take(pool, pages, axis=0)
                return jnp.swapaxes(got, 1, 2).reshape(
                    n * ps, pool.shape[1], pool.shape[3])

            kpos = lo * ps + jnp.arange(n * ps, dtype=jnp.int32)
            # the flash kernel or nothing: it raises, when the program is
            # built, for a chunk or table bucket its tiling rule refuses
            o = flash_attention_chunk_gqa(
                q[0], keys(kc), keys(vc), pp[0], kpos, sm_scale=scale,
                window=window)
            return jnp.dot(o.reshape(1, s, -1), wo), kc, vc

        out, kc, vc = apply_op("cohere_paged_attention", f, x, *cache,
                               block_tables, pos, count, first,
                               *self._weights())
        return out, (kc, vc)


class Cohere2MoeExperts(nn.Layer):
    """An expert layer's share: routing over all experts, the held
    experts' part, and the shared experts' mean."""

    def __init__(self, cfg: Cohere2MoeConfig):
        super().__init__()
        self.cfg = cfg
        if not 0 <= cfg.expert_offset <= cfg.num_experts - cfg.held:
            raise ValueError(
                f"experts {cfg.expert_offset}..{cfg.expert_offset + cfg.held}"
                f" are not among the {cfg.num_experts} routed")
        h, i = cfg.hidden_size, cfg.intermediate_size
        self.gate = _Weight((h, cfg.num_experts))
        self.experts = KimiK2Experts(cfg.held, h, i)
        # the num_shared_experts MLPs side by side: gate and up
        # concatenated along the width, down along its rows
        self.shared_experts = LlamaMLP(SimpleNamespace(
            hidden_size=h, intermediate_size=i * cfg.num_shared_experts,
            use_bias=False))

    def routed(self, x, live=None):
        """The held experts' part of x (B, S, hidden), and the layer's
        four expert counters; `live` (B, S) bool marks the real tokens."""
        cfg = self.cfg

        def f(xx, lv, wg, eg, eu, ed):
            b, s, h = xx.shape
            flat = xx.reshape(b * s, h)
            idx, w = route(flat, wg, jnp.zeros((cfg.num_experts,),
                                               jnp.float32),
                           top_k=cfg.num_experts_per_tok, scale=1.0,
                           norm=cfg.norm_topk_prob)
            y, counts = held_experts(flat, lv.reshape(b * s), idx, w, eg, eu,
                                     ed, offset=cfg.expert_offset)
            return y.reshape(b, s, h), counts

        if live is None:
            live = Tensor(jnp.ones(tuple(x.shape[:2]), bool))
        return apply_op("moe_held_experts", f, x, live, self.gate.weight,
                        self.experts.gate_proj, self.experts.up_proj,
                        self.experts.down_proj)

    def shared(self, x):
        n = self.cfg.num_shared_experts
        return apply_op("shared_mean", lambda a: a * (1.0 / n),
                        self.shared_experts(x))

    def forward(self, x, live=None):
        y, counts = self.routed(x, live)
        return y + self.shared(x), counts


class Cohere2MoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: Cohere2MoeConfig, index: int):
        super().__init__()
        self.input_layernorm = Cohere2LayerNorm(cfg.hidden_size,
                                                cfg.layer_norm_eps)
        self.self_attn = Cohere2MoeAttention(cfg, cfg.window_of(index))
        self.mlp = Cohere2MoeExperts(cfg)

    def forward(self, x):
        h = self.input_layernorm(x)
        return x + self.self_attn(h) + self.mlp(h)[0]

    def paged(self, x, cache, block_tables, group, kind, pos, count, first,
              live):
        h = self.input_layernorm(x)
        attn, cache = self.self_attn.paged(h, cache, block_tables, group,
                                           kind, pos, count, first)
        y, counts = self.mlp(h, live)
        return x + attn + y, cache, counts


class Cohere2MoeModel(nn.Layer):
    def __init__(self, cfg: Cohere2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                   cfg.hidden_size)
        self.layers = nn.LayerList([Cohere2MoeDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = Cohere2LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)


class Cohere2MoeForCausalLM(nn.Layer):
    def __init__(self, config: Cohere2MoeConfig):
        super().__init__()
        self.cfg = config
        self.model = Cohere2MoeModel(config)

    def _head(self, x):
        """norm(x) E^T x logit_scale: the embedding, tied."""
        scale = float(self.cfg.logit_scale)

        def f(a, table):
            lg = jax.lax.dot_general(
                a, table, (((a.ndim - 1,), (1,)), ((), ())))
            return lg if scale == 1.0 else lg * scale
        return apply_op("tied_head", f, self.model.norm(x),
                        self.model.embed_tokens.weight)

    def forward(self, input_ids):
        """Logits (B, S, V) of the full causal forward, no cache.
        Inference only (the experts' grouped kernels have no gradient)."""
        from ..core.autograd import no_grad
        with no_grad():
            x = self.model.embed_tokens(input_ids)
            for layer in self.model.layers:
                x = layer(x)
            return self._head(x)

    # ------------------------------------------- the engine's contract
    paged_counters = PAGED_COUNTERS

    def paged_cache_spec(self, page_size, dtype, kv_dtype=None, tp=1):
        """K pages and V pages of `(pages, KVH, page, D)` in the served
        type, in two layer groups: the full layers' (unbounded, group 0)
        and the window layers' (`sliding_window`)."""
        from ..kernels.paged_attention import (check_supported_paged,
                                               paged_page_bytes)
        cfg, dtype = self.cfg, jnp.dtype(dtype)
        if kv_dtype is not None:
            raise ValueError("this family's pages are kept in the served "
                             f"type; kv_dtype {kv_dtype!r} is not supported "
                             "(the windowed decode path was not written "
                             "for int8 pages)")
        if tp != 1:
            raise ValueError("attention is replicated over a chip's own "
                             "requests; a 'model' axis is not supported")
        kvh, d = cfg.num_key_value_heads, cfg.head_dim
        check_supported_paged((1, cfg.num_attention_heads, d),
                              (1, kvh, page_size, d), dtype)
        if FULL not in cfg.layer_types:
            raise ValueError("a model of window layers only is not "
                             "supported: group 0 of a paged cache spec is "
                             "the unbounded one")
        nbytes = paged_page_bytes(kvh, page_size, d, str(dtype))
        entries = (((kvh, page_size, d), dtype, None),) * 2
        if cfg.layer_groups is None:
            return PagedCacheSpec(entries, nbytes, nbytes)
        return PagedCacheSpec(
            entries, nbytes, nbytes, windows=(None, int(cfg.sliding_window)),
            layer_groups=cfg.layer_groups)

    def paged_forward(self, input_ids, paged_caches, block_tables, span):
        """The one paged entry (models/paged.py `PagedSpan`; a verify
        span is not written: the engine refuses a proposer over a windowed
        cache): logits at the chunk's last live position (prefill) or of
        every row (decode), the caches, and PAGED_COUNTERS summed over
        the layers."""
        from ..serving.lora.runtime import current_lora
        if span.kind not in ("decode", "prefill"):
            raise ValueError(f"span kind {span.kind!r} is not supported by "
                             f"this family")
        if current_lora() is not None:
            raise ValueError("this family's projections carry no adapter "
                             "hooks: an engine with a LoRA registry would "
                             "serve the base model under an adapter's name")
        cfg, m = self.cfg, self.model
        b, s = input_ids.shape
        positions, n_live, first = _span_positions(span, s)
        pos, count, first = Tensor(positions), Tensor(n_live), Tensor(first)
        # the real tokens: the experts neither compute nor count the rest
        live = apply_op(
            "span_live", lambda c: jnp.arange(s)[None, :] < c[:, None], count)
        groups = cfg.layer_groups or (None,) * cfg.num_hidden_layers
        x = m.embed_tokens(input_ids)
        caches = []
        counts = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
        for i, layer in enumerate(m.layers):
            x, cache, c = layer.paged(x, paged_caches[i], block_tables,
                                      groups[i], span.kind, pos, count,
                                      first, live)
            caches.append(cache)
            counts = counts + c._data
        windows = [cfg.window_of(i) for i in range(cfg.num_hidden_layers)]
        if span.kind == "prefill":
            # a live query at position p sees p + 1 keys; padding sees none
            seen = jnp.where(jnp.arange(s) < n_live[0], positions[0] + 1, 0)
            keys = jnp.stack([jnp.zeros((), jnp.int32), sum(
                jnp.sum(seen if w is None else jnp.minimum(seen, w))
                for w in windows)])
            x = apply_op(
                "chunk_last", lambda hh, ln: jax.lax.dynamic_slice_in_dim(
                    hh, jnp.asarray(ln, jnp.int32).reshape(()) - 1, 1,
                    axis=1), x, span.live)
        else:
            # a decoding row's context: `start` counts its tokens THROUGH
            # the input token (models/paged.py), 0 for a padded row
            ctx = jnp.asarray(span.start._data, jnp.int32)
            keys = jnp.stack([sum(
                jnp.sum(ctx if w is None else jnp.minimum(ctx, w))
                for w in windows), jnp.zeros((), jnp.int32)])
        counts = jnp.concatenate([counts, keys.astype(jnp.int32)])
        return self._head(x), caches, counts
