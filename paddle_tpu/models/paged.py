"""The model side of the serving engine's contract.

`ServingEngine` (and the draft-model proposer of `serving/spec`) programs
against three things a model gives, and names no other method of it:

  paged_cache_spec(page_size, dtype, kv_dtype=None, tp=1)
      -> PagedCacheSpec: what ONE layer's cache entry is. A page id names
      one row of every array of the entry, so the allocator, the radix
      cache, copy-on-write and the page-payload codec work on page ids
      whatever the entry holds: Llama's is (K pages, V pages[, their
      int8 scales]) of `(pages, KVH, page, D)`; a latent-attention
      model's is one `(pages, page, width)` array in which K and V are
      views of the same bytes. The engine sets the number of pages. The
      spec also says which layers attend to a window only (`windows`,
      `layer_groups`): those keep pages of their own.
  paged_forward(input_ids, caches, block_tables, span)
      -> (logits, caches, counters): the one paged entry over a SPAN of
      query positions: 1 a row to decode, 1 + K a row to verify drafts,
      S of one sequence to prefill a chunk (`PagedSpan`). `caches` is a
      list over layers of the entry's arrays as a tuple, returned with
      the same arity. `counters` is `()` or an int32 vector, one number
      for each name in `paged_counters`, summed over the layers.
  paged_counters
      the names of those numbers (a tuple, empty for a dense model). The
      engine adds each to its metrics counter of that name when it
      fetches the step's tokens: no sync of its own.

`decode_multi` is K decode steps in one trace over that entry: shared by
every model, so no family carries a copy of the scan.

A family implements the entry as it likes; both families here do it with
ONE span-shaped `paged(..., span)` a level under `paged_forward` (model,
decoder layer, attention), branching on `span.kind` only where the spans
differ: the positions' rope rows, the cache write, the attention.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor

__all__ = ["PAGED_ENTRY", "PagedSpan", "PagedCacheSpec", "decode_multi"]

# the one method name the engine's and the draft model's builders pass to
# `functional_call`
PAGED_ENTRY = "paged_forward"


@dataclass(frozen=True)
class PagedSpan:
    """The query positions of one paged launch.

    kind "prefill": ONE sequence's chunk. input_ids (1, S) sit at absolute
        positions start .. start + S - 1 (`start` a scalar: the tokens
        already in the cache), the first `live` of them real and the rest
        bucket padding; block_tables (P,). Logits (1, 1, V) at the last
        live position only.
    kind "decode": B rows of one token. input_ids (B, 1); `start` (B,)
        counts each row's tokens THROUGH that token (its position is
        start - 1; 0 marks a padded row); block_tables (B, P). Logits
        (B, 1, V).
    kind "verify": B rows of S = 1 + K tokens, the last emitted token and
        K drafts. `start` as for decode (through the FIRST input token),
        `live` (B,) the drafts of each row that are real. Logits (B, S, V).
    """
    kind: str
    start: Any
    live: Any = None


@dataclass(frozen=True)
class PagedCacheSpec:
    """One layer's cache entry. `entries` is a tuple, one for each array a
    page id names, of (shape of ONE page, dtype, partition spec of the
    whole array, page axis first, under tensor parallelism, or None).
    `page_bytes` is what one page of one layer costs over all shards and
    `page_bytes_shard` on one chip; at most four arrays (the engine's
    programs take four cache lists).

    LAYER GROUPS. `windows` names the groups of layers by what a query
    reads: None, every earlier position, or w, the last w of them (query
    q sees key j iff q - w < j <= q). Group 0 is the unbounded one and
    is what the engine's `num_pages` sizes. `layer_groups` gives each
    layer's group (None: every layer in group 0). Each group has a pool
    and a page list a sequence of its own: a windowed group gives a
    row's pages back as the row advances (`serving/kv_cache.py`
    `WindowGroup`). A model of one group takes its block tables as ever,
    (B, P) or a chunk's (P,); a model of G > 1 takes them stacked,
    (G, B, P) or (G, P), table g holding group g's pages at the SAME
    positions with the pad page where one was given back."""
    entries: Tuple[Tuple[tuple, Any, Optional[Any]], ...]
    page_bytes: int
    page_bytes_shard: int
    windows: Tuple[Optional[int], ...] = (None,)
    layer_groups: Optional[Tuple[int, ...]] = None


def decode_multi(model, input_ids, paged_caches, block_tables, seq_lens,
                 step_caps, eos_ids, key, *, k_steps, temperature=0.0,
                 top_k=0, top_p=1.0):
    """K decode iterations in ONE trace (multi-step device-side decode,
    ISSUE 13): a `lax.scan` over the model's single-token decode
    (`paged_forward` over a decode span) with IN-GRAPH sampling, so one
    compiled launch emits up to `k_steps` tokens per row instead of
    paying the host round trip per token.

    input_ids (B,) int32 — each row's last emitted token; seq_lens
    (B,) counts through that token (the decode span's convention);
    step_caps (B,) int32 — tokens row b may emit this launch (0 marks a
    padded batch row; the engine caps by remaining max_new_tokens);
    eos_ids (B,) int32 per-row EOS (-1 = none); key — ONE pre-drawn PRNG
    key, per-step keys are `fold_in`(key, step) so StepSupervisor retries
    replay the identical launch bit-for-bit.

    Per-row freeze masks: a row stops emitting once it hits its
    cap, its EOS, or a non-finite logits row (the per-launch NaN
    quarantine signal). Frozen rows stay in the batch at frozen
    (ids, seq_len) — each remaining step rewrites the SAME token's
    cache entry at the SAME position, the idempotent-rewrite contract the
    span writes already rely on — and their emitted-token slots are
    masked to the -1 sentinel. The loop carry threads the paged
    cache state through every step; the trip count is clamped to
    the tpu-lint A4 wedge cap (a 4096-iteration device-side loop
    once left the chip UNAVAILABLE for minutes; `k_steps` is
    engine-validated far below it, so the clamp is lint-provable,
    never load-bearing).

    Returns (tokens (B, K) int32 with -1 past each row's finish,
    n_emit (B,) int32, ok (B,) bool — False iff a LIVE step of that
    row produced non-finite logits — the updated caches, and the model's
    counters summed over the steps)."""
    from .generation import _sample_arr

    def arr(x):
        return x._data if isinstance(x, Tensor) else jnp.asarray(x)

    ids0 = arr(input_ids).astype(jnp.int32)
    bt = block_tables if isinstance(block_tables, Tensor) \
        else Tensor(jnp.asarray(block_tables))
    sl0 = arr(seq_lens).astype(jnp.int32)
    caps = arr(step_caps).astype(jnp.int32)
    eos = arr(eos_ids).astype(jnp.int32)
    key_a = arr(key)
    b = ids0.shape[0]
    caches0 = [tuple(t._data for t in kv) for kv in paged_caches]
    names = model.paged_counters
    counts0 = jnp.zeros((len(names),), jnp.int32) if names else ()

    def body(carry, j):
        ids, sl, active, n_emit, ok, caches, counts = carry
        caches_t = [tuple(Tensor(a) for a in kv) for kv in caches]
        logits, new_caches, step_counts = model.paged_forward(
            Tensor(ids[:, None]), caches_t, bt,
            PagedSpan("decode", Tensor(sl)))
        rows = logits._data[:, 0, :]
        fin = jnp.all(jnp.isfinite(rows), axis=-1)
        tok = _sample_arr(rows, jax.random.fold_in(key_a, j),
                          temperature, top_k, top_p)
        emit = jnp.logical_and(active, fin)
        # non-finite on a LIVE step poisons the row (frozen rows'
        # logits are discarded — they cannot quarantine anyone)
        ok = jnp.logical_and(ok, jnp.logical_or(fin, ~active))
        tok_out = jnp.where(emit, tok, jnp.int32(-1))
        n_emit = n_emit + emit.astype(jnp.int32)
        hit_eos = emit & (eos >= 0) & (tok == eos)
        active = emit & ~hit_eos & (n_emit < caps)
        ids = jnp.where(emit, tok, ids)
        sl = sl + emit.astype(jnp.int32)
        caches = [tuple(t._data for t in kv) for kv in new_caches]
        if names:
            counts = counts + step_counts
        return (ids, sl, active, n_emit, ok, caches, counts), tok_out

    carry0 = (ids0, sl0, caps > 0, jnp.zeros((b,), jnp.int32),
              jnp.ones((b,), bool), caches0, counts0)
    # trip count clamped to the A4 wedge cap inline, so tpu-lint can
    # prove the bound statically (the engine validates k_steps far
    # below it — the min() is never load-bearing at runtime)
    steps = jnp.arange(min(int(k_steps), 512), dtype=jnp.int32)
    (_, _, _, n_emit, ok, caches, counts), toks = jax.lax.scan(
        body, carry0, steps)
    new_caches = [tuple(Tensor(a) for a in kv) for kv in caches]
    return Tensor(toks.T), Tensor(n_emit), Tensor(ok), new_caches, counts
