"""ServingEngine: continuous-batching inference over the paged-KV kernels.

The XLA-shaped answer to Orca/vLLM/SGLang-style serving: iteration-level
scheduling, block-based KV management and the radix prefix cache run on
the host (scheduler.py / kv_cache.py / radix_cache.py), while all device
work funnels through a SMALL, FIXED set of compiled programs — one per
shape bucket — so continuous batching never triggers unbounded
recompilation. The programs are written against the MODEL-SIDE CONTRACT
of `models/paged.py` and name no other method of a model: what one
layer's cache entry is (`paged_cache_spec`: Llama's K and V pages, a
latent-attention model's one array a layer), ONE paged entry over a span
of query positions (`paged_forward`: 1 a row to decode, 1 + K to verify,
S of one sequence to prefill), and the counters a model adds to a launch's
outputs (`paged_counters`). What the bullets below say of Llama's kernels
is that family's implementation of the spans:

  * prefill CHUNK program, keyed by (chunk-length bucket, block-table
    bucket): processes one span of ONE padded prompt through a
    "prefill" span — rope at absolute positions,
    `paged_cache_write_range` at the chunk's offset, attention over the
    gathered paged prefix — and samples a token from the chunk's last
    live position (used only when the chunk completes the prompt).
    Whole-prompt prefill, chunked prefill, and radix prefix-cache hits
    are all THIS ONE program: a hit just starts at cache_len = matched
    tokens, so cache on/off cannot change program shapes (the
    determinism contract, SERVING.md);
  * decode program, keyed by (batch bucket, block-table-width bucket):
    one batched step through a "decode" span — per-row rope
    positions, `paged_cache_write` of the current token, Pallas
    `paged_attention_decode` over the block tables — plus sampling. The
    engine enqueues the NEXT step's decode launch before it fetches
    this one's tokens (ISSUE 34, `_plain_decode_step`): its input ids
    are read from this launch's tokens on the device (`_ids_after`), so
    the host's work between two launches runs under the device's;
  * VERIFY program (speculative decoding, ISSUE 5), keyed by
    ("verify", batch bucket, draft-length bucket, block-table bucket):
    when a `Proposer` is configured, the decode launch is replaced by
    a "verify" span — each row scores its last emitted
    token plus up to K drafted tokens in ONE launch, acceptance is
    resolved in-graph (greedy longest-prefix match, or exact one-hot
    rejection sampling for temperature > 0), and rejected drafts' KV
    pages roll back via `BlockAllocator.truncate_sequence`. K rides the
    program key like B and P, so the compile bound stays the bucket
    grid (`max_program_count`);
  * MULTI_DECODE program (multi-step decode, ISSUE 13), keyed by
    ("multi_decode", batch bucket, steps bucket, block-table bucket):
    with `decode_steps=K` (no proposer), the decode launch runs K
    iterations of the decode body inside ONE compiled `lax.scan`
    (`models/paged.py` `decode_multi`) — in-graph sampling on
    per-step keys folded from one pre-drawn key, per-step paged cache
    writes through the loop carry, and per-row EOS/step-cap/finiteness
    masks that freeze completed rows — so each emitted token stops
    paying a host round trip of its own. K rides the program key exactly
    like the verify program's.

Shape buckets pad up: a 19-token chunk runs in the 32-bucket, a decode
batch of 5 in the 8-bucket. The recompile counter (metrics) is bounded
by the bucket grid, which the engine test asserts.

Resilience layer (ISSUE 3, SERVING.md "Failure semantics"): per-request
deadlines/TTL and client `abort()`, cancelled at the next iteration
boundary in any state with valid KV donated to the radix cache;
bounded-queue admission control (`EngineOverloaded`); every compiled
launch runs under a `StepSupervisor` that retries transient device
errors with capped backoff, quarantines NaN-poisoned requests (each
program returns per-row finiteness flags computed in-graph — the jit
counterpart of the eager dispatch NaN hooks), and on unrecoverable
errors drains to a serializable snapshot a fresh engine resumes from
(`ServingEngine.from_snapshot`).

Determinism contract: greedy decode is deterministic, and a request's
tokens are bit-identical whether it runs alone or batched with others,
and whether its prefix came from the radix cache or its own prefill —
PROVIDED the same shape buckets are hit (XLA does not promise identical
rounding across different program shapes; rows within one program are
independent). The acceptance tests pin single buckets for exactly this
reason. Sampled decode draws from one engine-level key stream (final
chunks and decode steps draw; non-final chunks do not) and is
reproducible per (engine seed, arrival order) but not across different
interleavings.
"""
from __future__ import annotations

import inspect
import itertools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import no_grad
from ..core.tensor import Tensor
from ..jit.api import functional_call
from ..models.paged import PAGED_ENTRY, PagedSpan, decode_multi
from ..models.generation import _filter_logits, _sample_arr
from .. import profiler
from ..utils import faults
from ..utils.nan_inf import poison_scope
from .errors import (EngineFailure, EngineOverloaded,
                     SnapshotVersionError, check_feature_conflicts)
from .lora.adapter import AdapterNotLoaded
from .kv_cache import (BlockAllocator, BlocksExhausted, HostPageCorrupt,
                       WindowGroup,
                       HostPageLost, HostPagesExhausted, HostPageSlow,
                       HostPageStore, PAD_PAGE, decode_page_payload,
                       encode_page_payload)
from .metrics import ServingMetrics
from .program_cache import ProgramCache
from .radix_cache import RadixCache
from .scheduler import (Request, RequestState, Scheduler,
                        bump_request_counter)
from .supervisor import POISON, RetryPolicy, StepSupervisor, classify_failure
from .trace import FlightRecorder, RequestTracer

__all__ = ["ServingEngine", "SNAPSHOT_VERSION", "SNAPSHOT_MINOR",
           "check_snapshot_version", "tp_serving_mesh"]


def tp_serving_mesh(tp: int, devices=None):
    """The hybrid [data, pipe, sharding, sep, model] mesh a TP serving
    engine wants: model degree `tp` over the first `tp` devices (or an
    explicit device list). Thin wrapper over fleet's build_mesh so the
    axis names can never drift from the training stack's."""
    import jax as _jax
    from ..distributed.fleet.topology import build_mesh
    if devices is None:
        devices = _jax.devices()[:int(tp)]
    return build_mesh(mp=int(tp), devices=devices)

_engine_counter = itertools.count()

# Injectable monotonic timer for the per-launch TPOT samples (ISSUE 13):
# the drift tests monkeypatch this module attribute to pin launch
# durations; everything else sees time.perf_counter.
_perf_counter = time.perf_counter

SNAPSHOT_VERSION = 1
# Forward-compat MINOR (ISSUE 14): bumped when a build ADDS snapshot
# fields that older builds can safely ignore. A rolling restart mixes
# worker versions, so adoption must accept a same-major snapshot from
# a NEWER minor — unknown extra top-level keys warn-and-ignore instead
# of failing; only a MAJOR mismatch (a schema this build would
# misread) stays the loud, typed refusal.
# minor 2 (ISSUE 15): request records carry an "adapter" field; a
# lora-aware adopter REQUIRES the adapter loaded (typed refusal — never
# wrong-adapter), while pre-lora builds ignore the key.
# minor 3 (ISSUE 18): request records carry a "colocate" flag — a
# supervisor-pinned request that a prefill-role engine must decode
# locally instead of handing off (role-starved fallback); role-less
# builds ignore it.
SNAPSHOT_MINOR = 3
_SNAPSHOT_KNOWN_KEYS = frozenset(
    {"version", "minor", "reason", "rng_key", "requests",
     "flight_recorder"})


def check_snapshot_version(snapshot: dict):
    """Refuse a snapshot whose schema `version` stamp is not the one
    this build writes. Used by `from_snapshot` AND by the fleet's live
    migration — both must fail LOUD (typed) instead of resuming a
    schema they would silently misread. Same-major snapshots from a
    NEWER minor (extra fields) are accepted with a warning — the
    rolling-restart mixed-version case."""
    found = snapshot.get("version")
    if found != SNAPSHOT_VERSION:
        raise SnapshotVersionError(
            f"unsupported snapshot version {found!r} (this build "
            f"writes {SNAPSHOT_VERSION})",
            found=found, expected=SNAPSHOT_VERSION)
    minor = snapshot.get("minor", 0)
    extra = sorted(set(snapshot) - _SNAPSHOT_KNOWN_KEYS)
    if extra or (isinstance(minor, int) and minor > SNAPSHOT_MINOR):
        import warnings
        warnings.warn(
            f"snapshot from a newer same-major build (minor {minor!r} "
            f"vs {SNAPSHOT_MINOR}); ignoring unknown keys {extra}",
            RuntimeWarning, stacklevel=2)

# Fault-injection points (ISSUE 3; utils/faults.py). The step-exception
# points fire BEFORE the compiled launch, so an injected transient
# retries the identical, not-yet-executed launch; nan_logits poisons the
# per-row finiteness flags AFTER the launch (the in-graph isfinite check
# is exercised for real by tests that NaN a weight); deadline_storm
# returns seconds of forward clock skew applied at the next boundary.
FAULT_CHUNK = faults.register_point("serving.engine.prefill_chunk")
FAULT_DECODE = faults.register_point("serving.engine.decode_step")
FAULT_NAN = faults.register_point("serving.engine.nan_logits")
FAULT_STORM = faults.register_point("serving.engine.deadline_storm")
# Speculative decoding (ISSUE 5): verify_step mirrors decode_step (fires
# BEFORE the verify launch — an injected transient retries the identical
# program); draft_storm replaces the proposer's drafts with the payload
# (callable(reqs, k) -> drafts, or True for seeded garbage) — the
# mismatch storm MUST be output-invariant under greedy acceptance, which
# the soak asserts. nan_logits covers the verify path too.
FAULT_VERIFY = faults.register_point("serving.engine.verify_step")
FAULT_DRAFT = faults.register_point("serving.spec.draft_storm")
# Multi-step decode (ISSUE 13): mirrors decode_step — fires BEFORE the
# launch, so an injected transient retries the identical K-step program.
FAULT_MULTI = faults.register_point("serving.engine.multi_decode_step")

# What a program family's launch is called and fires, beside its inputs:
# family -> (the launch span `serving.<name>`, which is also the
# supervisor's label and the request tracer's span; the letters of its
# bucket dims in the step record's program label; its fault point)
_LAUNCHES = {
    "chunk": ("prefill_chunk", "SP", FAULT_CHUNK),
    "decode": ("decode_step", "BP", FAULT_DECODE),
    "multi_decode": ("multi_decode_step", "BKP", FAULT_MULTI),
    "verify": ("verify_step", "BKP", FAULT_VERIFY),
}

# What an engine whose model has a windowed layer group counts beside the
# model's own counters (PERF.md section 3): each step, over the DECODING
# rows, the pages the windowed groups hold and the pages they would
# hold without their windows (the unbounded group's count a windowed
# group); and the pages given back as rows advanced.
_WINDOW_COUNTERS = ("kv_window_pages_held", "kv_window_pages_full",
                    "kv_window_pages_released")

# What an engine whose layers decode through `paged_attention_decode`
# counts a plain decode launch (PERF.md section 3), by the kernel's own
# rules (`paged_decode_page_counts`): the page slots of the kernel's live
# steps, and the pages it copies, over the launch's rows and those layers.
_GATHER_COUNTERS = ("decode_kv_tile_pages", "decode_kv_pages_copied")

# Ceiling on decode_steps (K): each launch runs K decode iterations in
# one device-side scan, and a device loop of 4096 iterations once left
# a chip UNAVAILABLE for minutes (the tpu-lint A4 wedge cap is 512).
# 64 leaves an order of magnitude of headroom while still amortizing
# the per-launch host round trip ~64x.
MAX_DECODE_STEPS = 64


class _DecodeLaunch:
    """One plain decode launch from its enqueue to its fetch: the rows
    it carries (`row`: request -> its row; a row that finishes meanwhile
    stays in it, and its token is never read), each row's length through
    its input token, the launch's tokens, finiteness flags and model
    counters still on the device, when it was enqueued (None for a
    launch enqueued AHEAD: its time runs from the fetch of the launch
    before it) and its label for the step record of the step that
    returns its tokens."""

    __slots__ = ("reqs", "row", "dims", "sl", "out", "t0", "t_tr", "label")

    def __init__(self, reqs, dims, sl, out, t0, t_tr, label):
        self.reqs, self.dims, self.sl, self.out = reqs, dims, sl, out
        self.row = {r: i for i, r in enumerate(reqs)}
        self.t0, self.t_tr, self.label = t0, t_tr, label

    @property
    def ahead(self) -> bool:
        return self.t0 is None


@jax.jit
def _ids_after(toks, src, fresh):
    """The (B, 1) input ids of a decode launch enqueued while the one
    before it is in flight: row i continues row `src[i]` of that launch,
    whose token is `toks[src[i]]`, on the device and not yet fetched; or,
    where `src[i]` is -1, has just finished its prefill and starts from
    `fresh[i]`, the first token the host already holds. `src` and `fresh`
    are int32 arrays built with NumPy."""
    took = jnp.take(toks, jnp.maximum(src, 0), mode="clip")
    return jnp.where(src >= 0, took, fresh)[:, None]


def _bucket_for(value: int, buckets: List[int]) -> int:
    for b in buckets:
        if value <= b:
            return b
    raise ValueError(f"{value} exceeds largest bucket {buckets[-1]}")


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


class _HostSpillBridge:
    """RadixCache.spill implementation over ONE engine's device caches
    and its HostPageStore (protocol: RadixCache.__init__). The tree
    stays device-blind; all array traffic funnels through here.

    demote() gathers each device page's rows across every layer into
    one encoded payload (a real device->host fetch per array — the
    eviction path already tolerates host latency); promote() decodes
    every payload FIRST (a corrupt page must fail before any device
    page is claimed), then allocates device pages and enqueues per-
    layer `.at[pid].set(...)` scatters WITHOUT a host sync — jax
    dispatch is async, so the copies overlap the prefill launch the
    scheduler is about to build, and the device stream orders them
    before any kernel that reads the pages (the "in-flight" residency
    window is exactly this enqueued-not-fetched state).
    """

    def __init__(self, engine: "ServingEngine"):
        self.eng = engine

    def host_free(self) -> int:
        return self.eng.host_store.num_free

    def holds(self, hid: int) -> bool:
        return self.eng.host_store.holds(hid)

    def demote(self, pids):
        """Device pages -> host payloads. Returns the host ids, or None
        when the host pool ran out mid-batch (partial puts roll back, so
        a refused demotion leaks nothing — the caller drops instead)."""
        store = self.eng.host_store
        hids = []
        try:
            for pid in pids:
                hids.append(store.put(
                    self.eng._gather_page_payload(pid)))
        except HostPagesExhausted:
            for hid in hids:
                store.decref(hid)
            return None
        return hids

    def promote(self, hids):
        """Host payloads -> fresh device pages (refcount 1 each — the
        tree ref). Returns None when the device pool is dry (recompute
        beats evicting for a maybe-hit); HostPageError kinds propagate
        AFTER the fault counter bump, with no device page claimed."""
        eng = self.eng
        c = eng.metrics.counters
        payloads = []
        try:
            for hid in hids:
                payloads.append(
                    decode_page_payload(eng.host_store.get(hid)))
        except HostPageSlow:
            c["host_spill_slow"] += 1
            raise
        except HostPageCorrupt:
            c["host_spill_corrupt"] += 1
            raise
        except HostPageLost:
            c["host_spill_lost"] += 1
            raise
        try:
            pids = eng.allocator._alloc_pages(len(hids))
        except BlocksExhausted:
            return None
        for pid, arrays in zip(pids, payloads):
            eng._scatter_page_payload(pid, arrays)
        return pids

    def release(self, hids):
        """Drop the tree's host refs. Tolerates ids the store forgot
        after a host_spill.lost fault — the lost slot is already free,
        and a decref there would double-free a reused slot."""
        store = self.eng.host_store
        for hid in hids:
            if store.holds(hid):
                store.decref(hid)


class ServingEngine:
    """Continuous-batching engine over a causal LM with paged-KV decode.

    model: a model of the paged contract (`models/paged.py`:
    `paged_cache_spec`, `paged_forward`, `paged_counters`) — a "prefill"
    span for (chunked) prompt processing and a "decode" span for the
    batched decode step, both over the engine-owned paged caches
    (plus a "verify" span when speculative decoding is on).
    enable_prefix_cache turns the radix tree on (default); off, the
    engine behaves like PR 1 plus chunked prefill.

    proposer (serving.spec.Proposer, optional) enables speculative
    decoding: up to `spec_k` draft tokens per decoding request are
    verified per step in one ("verify", B, K, P) launch; greedy output
    is token-identical to plain decode (drafting only changes how many
    launches it takes), and `spec_buckets` is the K axis of the
    program grid.

    decode_steps=K (ISSUE 13) runs K decode iterations inside ONE
    compiled ("multi_decode", B, K, P) launch — a device-side scan
    over the decode body with in-graph sampling, per-step paged cache
    writes, and per-row EOS/max-token/finiteness masks that freeze
    completed rows — so each emitted token stops paying a host round
    trip of its own. Greedy output is token-identical to K=1 (the
    per-step math is the same program body; rows are independent);
    the scheduler admits/preempts at K-step boundaries and the decode
    token budget is charged xK; abort/TTL take effect at the next
    K-boundary with the launch's tokens delivered; NaN quarantine is
    per LAUNCH (a poisoned row delivers none of the launch's tokens).
    Mutually exclusive with `proposer` — both multiply tokens per
    launch. `multi_buckets` is the K axis of the program grid.

    Quantized decode path (ISSUE 6):
    * kv_dtype="int8" stores KV pages as int8 with fp32 per-slot
      scales riding the SAME page ids (quantize-on-write inside the
      compiled programs, dequantize-in-kernel/-gather on read) — the
      page payload halves, so at a fixed `kv_pool_bytes` the pool
      holds ~2x the pages (2D/(D+4) exactly; paged_page_bytes is the
      math's single source). All page bookkeeping (CoW fork, radix
      donation, truncate_sequence rollback, snapshot/resume) is
      host-side and byte-level, so it is bit-identical across
      kv_dtype — only the attention arithmetic changes, within the
      documented rel-err budget.
    * wq="int8" converts the model's decode-regime projections
      (MLP gate/up/down + LM head) to int8 weights IN PLACE
      (nn.quant.quantize_for_serving) before the state snapshot, so
      every program serves them through the fused Pallas
      dequant-matmul (kernels/quant_matmul.py). The conversion
      mutates `model` — pass a model dedicated to this engine.
    * kv_pool_bytes sizes num_pages from an HBM byte budget instead
      of a page count (num_pages = budget // page_bytes) — the knob
      the capacity-doubling acceptance test turns.
    Both ride the program-cache keys, so engines with different quant
    configs sharing a process never collide, and the compile bound
    stays the bucket grid.

    Multi-LoRA serving (ISSUE 15): pass `lora` (a
    serving.lora.AdapterRegistry built for this model's dims) and tag
    requests with `add_request(adapter=...)`. Adapter A/B factors live
    PAGED in the registry's device pools (BlockAllocator discipline,
    LRU eviction of idle adapters, live-request refcount pinning);
    every program takes the pools/page-tables/per-row slot ids as
    call-time INPUTS, gathers the fixed-shape slot stacks in-graph and
    applies each row's own delta through the masked segment-bmm kernel
    (kernels/lora_matmul.py) — rows of one launch may mix adapters,
    load/unload never recompiles, and only the static layout signature
    rides the program key. The radix key is adapter-namespaced
    (prefixes never cross adapters) and snapshots carry the adapter
    (adoption requires it loaded — typed refusal otherwise). Mutually
    exclusive with `proposer` and `mesh` (documented in SERVING.md).

    Tensor-parallel serving (ISSUE 8): pass `mesh` (a hybrid
    [data, pipe, sharding, sep, model] jax Mesh with model degree tp)
    to shard attention heads, the paged KV pool (page CONTENTS,
    including int8 scale pages — page IDS stay global) and the
    MLP/LM-head weights over 'model'. The scheduler, BlockAllocator
    and RadixCache are host-side and rank-replicated, so every
    paging/refcount/radix trace is bit-identical to the single-chip
    engine by construction; all three program families compile under
    jax.jit with GSPMD shardings (column-parallel QKV/gate-up,
    row-parallel O/down with psum, paged attention per shard over its
    own KVH/tp kv heads — kernels.paged_attention_decode_tp), and the
    mesh shape rides the program-cache key. `kv_pool_bytes` stays a
    PER-CHIP budget: head-sharded pages cost kv_page_bytes_shard per
    chip, so capacity at fixed per-chip bytes scales ~x tp.
    """

    def __init__(self, model, *, num_pages: int = 128, page_size: int = 16,
                 max_batch_size: int = 8, token_budget: int = 512,
                 batch_buckets: Optional[List[int]] = None,
                 prefill_buckets: Optional[List[int]] = None,
                 pages_buckets: Optional[List[int]] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 max_retained_finished: int = 1024,
                 enable_prefix_cache: bool = True,
                 max_queue_len: Optional[int] = None,
                 default_ttl_s: Optional[float] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 clock=None,
                 proposer=None, spec_k: int = 4,
                 spec_buckets: Optional[List[int]] = None,
                 decode_steps: int = 1,
                 multi_buckets: Optional[List[int]] = None,
                 kv_dtype: Optional[str] = None,
                 wq: Optional[str] = None,
                 kv_pool_bytes: Optional[int] = None,
                 host_spill_pages: int = 0,
                 mesh=None,
                 lora=None,
                 role: str = "both",
                 compile_cache=None,
                 trace=None, trace_ring: int = 512,
                 flight_recorder_steps: int = 128):
        cfg = model.cfg
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', got "
                             f"{kv_dtype!r}")
        if wq not in (None, "int8", "int4"):
            raise ValueError(f"wq must be None, 'int8' or 'int4', got "
                             f"{wq!r}")
        self.kv_dtype = kv_dtype
        self.wq = wq
        # --- disaggregated serving role (ISSUE 18) ---
        # "both" (default) is the co-located engine. "prefill": every
        # request that completes its prefill finishes with reason
        # "handoff" instead of entering the decode batch — its
        # block-aligned pages sit donated in the radix tree for the
        # fleet's kv_pull, and `handoff_prefix_len` on the request
        # records the span; requests adopted with a "colocate" pin
        # decode locally anyway (role-starved fallback). "decode" is a
        # routing tag only — the engine behaves exactly like "both"
        # (it must re-prefill prompt tails and failed handoffs).
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be 'both', 'prefill' or "
                             f"'decode', got {role!r}")
        self.role = role
        # --- tensor parallelism (ISSUE 8) ---
        # mesh: a hybrid [data, pipe, sharding, sep, model] jax Mesh (or
        # any mesh with a 'model' axis). Attention heads, the paged KV
        # pool's page CONTENTS (including int8 scale pages) and the
        # MLP/LM-head weights shard over 'model'; the scheduler,
        # BlockAllocator and RadixCache stay host-side and
        # rank-replicated — page IDS are global, so every paging/
        # refcount/radix decision is bit-identical to the single-chip
        # engine by construction.
        self.mesh = mesh
        self.tp = (int(dict(mesh.shape).get("model", 1))
                   if mesh is not None else 1)
        # the central capability table (serving/errors.py, ROADMAP item
        # 4): every pairwise feature conflict is ONE check against ONE
        # table — the scattered per-feature raises this replaces could
        # (and did) drift apart as features landed in different PRs
        active = set()
        if proposer is not None:
            active.add("proposer")
        if int(decode_steps) > 1:
            active.add("multi_step_decode")
        if lora is not None:
            active.add("lora")
        if self.tp > 1:
            active.add("tensor_parallel")
        if int(host_spill_pages) > 0:
            active.add("host_spill")
        if not enable_prefix_cache:
            active.add("no_prefix_cache")
        if role == "prefill":
            active.add("prefill_role")
        check_feature_conflicts(active)
        if wq is not None:
            # IN PLACE, before the state snapshot below: the quantized
            # buffers (int8 qweight + fp scale) replace the fp weights
            # in state_dict, so every compiled program reads 1 byte per
            # weight element through the fused dequant-matmul
            from ..nn.quant import quantize_for_serving
            self.num_wq_layers = quantize_for_serving(
                model, algo=f"weight_only_{wq}")
        else:
            self.num_wq_layers = 0
        self.model = model
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.page_size = int(page_size)
        wdtype = next(t._data.dtype for t in model.state_dict().values()
                      if jnp.issubdtype(t._data.dtype, jnp.floating))
        # what ONE layer's cache entry is comes from the model
        # (models/paged.py): the arrays a page id names, and the bytes
        # one page costs in THIS engine (int8 pages + scales, or the
        # model dtype's full-width pages) — the capacity gauge and the
        # kv_pool_bytes sizing below both hang off it. Under TP a page's
        # contents are head-sharded, so one chip pays only the per-SHARD
        # bytes. The model raises here, at construction, where its
        # kernels' static constraints refuse the (per-shard) geometry.
        self._kv_dtype_name = (kv_dtype if kv_dtype is not None
                               else str(wdtype))
        spec = model.paged_cache_spec(self.page_size, wdtype,
                                      kv_dtype=kv_dtype, tp=self.tp)
        self.kv_page_bytes = spec.page_bytes
        self.kv_page_bytes_shard = spec.page_bytes_shard
        # the layer groups (models/paged.py): group 0 is unbounded and is
        # what `num_pages` / `kv_pool_bytes` size; a windowed group's
        # pool follows from the batch and the chunk budget below, because
        # a row's need of it is bounded (kv_cache.py `WindowGroup`)
        self._layer_groups = tuple(spec.layer_groups
                                   or (0,) * self.num_layers)
        if spec.windows[0] is not None or \
                len(self._layer_groups) != self.num_layers or \
                set(self._layer_groups) != set(range(len(spec.windows))):
            raise ValueError(
                "a paged cache spec names an unbounded group first and a "
                f"group for every layer; got windows {spec.windows} over "
                f"layers {self._layer_groups}")
        if len(spec.windows) > 1:
            check_feature_conflicts(active | {"windowed_cache"})
        if kv_pool_bytes is not None:
            # size the pool from a PER-CHIP HBM byte budget: the page
            # count is what kv_dtype="int8" roughly doubles and TP
            # multiplies by ~tp at fixed per-chip bytes (head-sharded
            # pages cost kv_page_bytes_shard per chip)
            num_pages = max(2, int(kv_pool_bytes)
                            // self.kv_page_bytes_shard)
        self.num_pages = int(num_pages)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self._key = jax.random.PRNGKey(seed)
        # non-final chunks pass a fixed key (their sampled token is
        # discarded) so the engine's key stream advances once per token
        # actually emitted, not once per chunk
        self._null_key = jax.random.PRNGKey(0)

        # serving weights are immutable: snapshot the flat {name: array}
        # view once instead of re-walking state_dict() every step.
        # Under TP each weight is device_put per its mark_sharding spec
        # (column-parallel QKV/gate-up split the out dim, row-parallel
        # O/down the in dim, the vocab embedding its vocab dim);
        # spec-less buffers (rope tables, quant scales without an out
        # shard) replicate. jit then reads the argument shardings — no
        # per-weight constraints needed inside the programs.
        self._state = {}
        for k, t in model.state_dict().items():
            self._state[k] = self._place(t._data,
                                         getattr(t, "_spec", None))

        dtype = next(a.dtype for a in self._state.values()
                     if jnp.issubdtype(a.dtype, jnp.floating))

        # longest sequence a request may ever reach (rope table and page
        # supply both bound it)
        self.max_seq_len = min(int(cfg.max_position_embeddings),
                               (self.num_pages - 1) * self.page_size)
        max_pages_per_seq = -(-self.max_seq_len // self.page_size)

        self.batch_buckets = sorted(batch_buckets or
                                    _pow2_buckets(1, int(max_batch_size)))
        self.prefill_buckets = sorted(
            prefill_buckets or _pow2_buckets(
                min(16, self.max_seq_len), self.max_seq_len))
        self.pages_buckets = sorted(
            pages_buckets or _pow2_buckets(
                min(2, max_pages_per_seq), max_pages_per_seq))
        # the widest block table a decode program supports also bounds
        # how long any sequence may grow
        self.max_seq_len = min(self.max_seq_len,
                               self.pages_buckets[-1] * self.page_size)
        if self.prefill_buckets[-1] > self.max_seq_len:
            raise ValueError("prefill bucket exceeds max sequence length")

        # --- speculative decoding (ISSUE 5) ---
        # proposer drafts up to spec_k tokens per decoding request per
        # step; the bucketed ("verify", B, K, P) program scores them in
        # one launch. K rides the program-cache KEY (like B and P), so
        # the compile count stays bounded by the grid — spec_buckets is
        # the K axis of that grid.
        self.proposer = proposer
        self.spec_k = int(spec_k)
        if proposer is not None and self.spec_k < 1:
            raise ValueError("spec_k must be >= 1 with a proposer")
        self.spec_buckets = sorted(
            spec_buckets or _pow2_buckets(1, max(1, self.spec_k))) \
            if proposer is not None else []
        if self.spec_buckets and self.spec_buckets[-1] != self.spec_k:
            raise ValueError(
                f"largest spec bucket {self.spec_buckets[-1]} must equal "
                f"spec_k {self.spec_k}")

        # --- multi-step decode (ISSUE 13) ---
        # decode_steps=K runs K decode iterations inside ONE compiled
        # ("multi_decode", B, K, P) launch (lax.scan over the decode
        # body, in-graph sampling + per-row freeze masks) — the plain-
        # decode counterpart of the verify program. K rides the
        # program-cache key with multi_buckets as its grid axis, so the
        # compile bound stays the bucket grid. Mutually exclusive with
        # speculative decoding per launch: both multiply tokens per
        # launch and would double-charge the token budget.
        self.decode_steps = int(decode_steps)
        if self.decode_steps < 1:
            raise ValueError("decode_steps must be >= 1")
        if self.decode_steps > MAX_DECODE_STEPS:
            raise ValueError(
                f"decode_steps {self.decode_steps} exceeds "
                f"MAX_DECODE_STEPS {MAX_DECODE_STEPS} (device-side loop "
                f"trip counts are capped well under the 512-iteration "
                f"wedge cap — tpu-lint A4)")
        # decode_steps x proposer conflicts via the capability table
        # (checked above — serving/errors.py FEATURE_CONFLICTS)
        self.multi_buckets = sorted(
            multi_buckets or _pow2_buckets(1, self.decode_steps)) \
            if self.decode_steps > 1 else []
        if self.multi_buckets and self.multi_buckets[-1] != self.decode_steps:
            raise ValueError(
                f"largest multi bucket {self.multi_buckets[-1]} must "
                f"equal decode_steps {self.decode_steps}")

        # --- multi-LoRA adapter serving (ISSUE 15) ---
        # lora: an AdapterRegistry (serving.lora). Requests carry an
        # adapter NAME (`add_request(adapter=...)`); each launch passes
        # the registry's paged pools + page tables + per-row slot ids
        # as program INPUTS and the programs gather/apply each row's
        # own adapter delta in-graph — rows of one launch may mix
        # adapters, and load/unload/evict never recompiles (only the
        # static layout signature rides the program key, below).
        self.lora = lora
        # lora x proposer / lora x tensor_parallel conflicts via the
        # capability table (checked above)

        # the scheduler's two bounds, which size a windowed group's pool too
        rows, budget = self.batch_buckets[-1], \
            min(token_budget, self.prefill_buckets[-1])
        self.allocator = BlockAllocator(
            self.num_pages, self.page_size,
            [WindowGroup(WindowGroup.pages_for(w, self.page_size, rows,
                                               budget), self.page_size, w)
             for w in spec.windows[1:]])
        # a model with a windowed group donates nothing: a prefix hit
        # would have to bring the window's pages before the match point,
        # which were given back (SERVING.md "Layer groups")
        self.radix = (RadixCache(self.allocator)
                      if enable_prefix_cache and not self.allocator.windows
                      else None)
        self.scheduler = Scheduler(
            self.allocator, max_batch_size=rows, token_budget=budget,
            max_prompt_len=self.max_seq_len,
            prefix_cache=self.radix,
            max_queue_len=max_queue_len)
        if proposer is not None:
            # verify tokens draw from the same per-step token budget
            # prefill chunks compete for (SERVING.md bucketing note)
            self.scheduler.decode_token_cost = 1 + self.spec_k
        elif self.decode_steps > 1:
            # each decoding request may emit up to K tokens per launch:
            # charge the budget xK so admission/preemption decisions at
            # K-step boundaries see the true per-launch token traffic
            self.scheduler.decode_token_cost = self.decode_steps
        # --- resilience (ISSUE 3) ---
        # deadlines use an injectable clock (tests/soak pass a fake one;
        # the fault harness adds skew) so expiry stays deterministic
        self._clock = clock if clock is not None else time.monotonic
        self._clock_skew = 0.0
        self.default_ttl_s = default_ttl_s
        self.supervisor = StepSupervisor(
            policy=retry_policy,
            on_retry=self._on_step_retry,
            retryable=self._caches_alive)
        self.failed = False
        self.last_snapshot: Optional[dict] = None
        # per-engine provider name: two live engines must not shadow each
        # other in profiler.counters(), nor unregister each other
        self.metrics = ServingMetrics(
            name=f"serving-{next(_engine_counter)}").register()
        if self.lora is not None:
            # registry lifecycle counters land in THIS engine's
            # auto-exposed metrics (loads done before attach carry in)
            self.lora.bind_counters(self.metrics.counters)
        # --- observability (ISSUE 10) ---
        # Per-request tracing is OFF by default and free when off:
        # every hook is guarded by ONE `self.tracer is None` check, so
        # the default hot path allocates nothing trace-related.
        # trace=True builds a private RequestTracer; a fleet passes the
        # SAME RequestTracer instance to every replica so a migrated
        # request keeps one trace across engines. The flight recorder
        # is always on — one small dict per non-idle step, bounded ring
        # — and rides every snapshot so postmortems carry context.
        if trace is True:
            self.tracer: Optional[RequestTracer] = RequestTracer(
                max_completed=trace_ring)
        elif trace:
            self.tracer = trace
        else:
            self.tracer = None
        self.recorder = FlightRecorder(flight_recorder_steps)
        self._cur_rids = ()          # requests in the launch being run
        self._step_ev = {"programs": []}
        self._step_t0: Optional[float] = None
        # the running step's number: metadata of the `serving.step`
        # span, `step` of the flight recorder's record and of the
        # RequestTracer's launch spans (the join between the clocks)
        self._step_no = 0
        self._last_launch_s: Optional[float] = None
        # the plain decode launch enqueued ahead and not yet fetched
        # (`_plain_decode_step`), and when the last decode launch's
        # tokens reached the host (engine timer, tracer clock): a launch
        # enqueued ahead is timed from there
        self._flight: Optional[_DecodeLaunch] = None
        self._fetched = (0.0, 0)

        # the pool: one list over the layers for each array of the
        # model's cache entry (Llama: K pages, V pages and, for int8,
        # their scale pages, head-sharded over 'model' under TP while page
        # IDS stay global; a latent-attention model: ONE array a layer).
        # The compiled programs take four cache lists unconditionally, so
        # every entry shares one program shape: the lists an entry does
        # not fill are empty pytrees.
        group_pages = [self.num_pages] + [g.pool.num_pages
                                          for g in self.allocator.windows]
        pools = [[self._place(jnp.zeros((group_pages[g],) + tuple(page), dt),
                              pspec)
                  for g in self._layer_groups]
                 for page, dt, pspec in spec.entries]
        pools += [[] for _ in range(4 - len(pools))]
        self._k_caches, self._v_caches, self._k_scales, self._v_scales = \
            pools
        # the model's own counters (models/paged.py `paged_counters`): a
        # small int32 vector each program returns beside the step's
        # tokens, added to the metrics counters of those names where the
        # tokens are fetched. A dense model names none and its programs
        # return an empty pytree there.
        self._model_counters = tuple(model.paged_counters)
        # a layer whose entry is K and V pages of (KVH, page, D) decodes
        # through `paged_attention_decode` (models/paged.py): the shape
        # its gather is counted by — KVH a shard's under TP — and each
        # group's (window, layers)
        page, cache_dtype, pspec = spec.entries[0]
        self._gather_shape = None
        if len(page) == 3:
            self._gather_shape = (
                page[0] // (self.tp if pspec is not None else 1), page[2],
                cache_dtype, tuple((win, self._layer_groups.count(g))
                                   for g, win in enumerate(spec.windows)))
        for name in self._model_counters + (
                _WINDOW_COUNTERS if self.allocator.windows else ()) + (
                _GATHER_COUNTERS if self._gather_shape else ()):
            self.metrics.counters.setdefault(name, 0)
        # bytes-moved accounting (ServingMetrics): one token's K+V
        # across every layer, scales included — GLOBAL bytes (the sum
        # over shards); per-chip traffic is this / tp
        self.kv_bytes_per_token = (self.num_layers * self.kv_page_bytes
                                   // self.page_size)
        self.metrics.set_kv_info(
            kv_dtype=self.kv_dtype or str(dtype),
            page_bytes=self.kv_page_bytes,
            pool_bytes=self.kv_page_bytes * self.num_pages,
            bytes_per_token=self.kv_bytes_per_token,
            tp_degree=self.tp,
            page_bytes_shard=self.kv_page_bytes_shard,
            pool_bytes_shard=self.kv_page_bytes_shard * self.num_pages)

        # --- tiered KV: host-RAM spill tier (ISSUE 17) ---
        # host_spill_pages > 0 puts a HostPageStore under the radix
        # cache: LRU eviction DEMOTES pages (values + int8 scale rows)
        # to host payloads instead of freeing them, and a later match
        # PROMOTES them back with an async host->device copy overlapped
        # with the prefill launch. 0 (the default) is bit-for-bit the
        # pre-spill engine. One host page carries a radix page's K+V
        # across EVERY layer (scales included): num_layers x
        # kv_page_bytes — the whole per-layer stack is the demote unit.
        self.host_spill_pages = int(host_spill_pages)
        if self.host_spill_pages < 0:
            raise ValueError("host_spill_pages must be >= 0")
        # host_spill x tensor_parallel / x no_prefix_cache conflicts
        # via the capability table (checked above)
        self.host_page_bytes = self.num_layers * self.kv_page_bytes
        if self.host_spill_pages:
            self.host_store: Optional[HostPageStore] = HostPageStore(
                self.host_spill_pages)
            self.radix.set_spill(_HostSpillBridge(self))
            self.metrics.set_host_info(
                pool_pages=self.host_spill_pages,
                page_bytes=self.host_page_bytes)
        else:
            self.host_store = None

        self.requests: Dict[int, Request] = {}
        self._finished_order: List[int] = []
        # a long-lived server must not accumulate every finished request
        # (same unbounded-growth class as the jit fallback registry):
        # only the most recent `max_retained_finished` stay readable
        self.max_retained_finished = int(max_retained_finished)
        self.num_evicted_finished = 0
        # the unified ProgramCache (ISSUE 8): one keyed store for the
        # chunk/decode/verify families with per-family bucket-grid
        # bounds (whole-prompt prefill and chunked prefill are ONE
        # family — the chunk program — so "prefill" compiles count
        # under "chunk" by design; the draft-model proposer runs its
        # own cache with its own families)
        self.programs = ProgramCache(
            on_compile=lambda: self.metrics.on_recompile())
        self.programs.register_family(
            "chunk", lambda: (len(self.prefill_buckets)
                              * len(self.pages_buckets)))
        self.programs.register_family(
            "decode", lambda: (len(self.batch_buckets)
                               * len(self.pages_buckets)))
        self.programs.register_family(
            "verify", lambda: (len(self.batch_buckets)
                               * len(self.spec_buckets)
                               * len(self.pages_buckets)))
        self.programs.register_family(
            "multi_decode", lambda: (len(self.batch_buckets)
                                     * len(self.multi_buckets)
                                     * len(self.pages_buckets)))
        # caches only pay off donated on a real accelerator; CPU jit
        # warns per call and keeps the copy anyway. Scale lists donate
        # too (empty pytrees for full-width KV — a no-op there).
        self._donate = (1, 2, 3, 4) if jax.default_backend() == "tpu" \
            else ()
        # quant config AND the mesh shape ride every program-cache key:
        # two engines with different kv_dtype/wq/TP degree in one
        # process must never share a compiled program, and the
        # bucket-grid compile bound is per-engine (one mesh shape per
        # engine) so the key suffix costs nothing. The sampling config
        # rides too (B1): temperature/top_k/top_p are closed over as
        # Python constants by every builder, so without the key axis a
        # persistent CompileCache entry written at one temperature
        # would be served to a restarted worker running another
        self._qkey = (self.kv_dtype or "kv_full", self.wq or "w_full",
                      ("tp", self.tp),
                      ("sampling", self.temperature, self.top_k,
                       self.top_p))
        if self.lora is not None:
            # the STATIC lora layout (slots x rank buckets x page
            # geometry) rides every program key; adapter ids never do
            # — loading/unloading adapters can never grow the grid
            self._qkey = self._qkey + (self.lora.signature(),)

        # --- persistent compile cache (ISSUE 14) ---
        # compile_cache: a directory path (a CompileCache is built over
        # it, fingerprinted with THIS engine's model/pool geometry) or
        # a ready CompileCache instance (caller owns the fingerprint —
        # sharing one instance across engines also shares its
        # counters). Misses in the ProgramCache then consult disk
        # before building, and `save_compile_cache()` persists every
        # launched program so a restarted worker skips the bucket-grid
        # compile storm.
        if compile_cache is not None:
            from .compile_cache import CompileCache
            if not isinstance(compile_cache, CompileCache):
                compile_cache = CompileCache(
                    str(compile_cache), extra=self._geometry_signature())
            self.programs.disk = compile_cache
        self._sync_compile_cache_counters()

    def _caches_alive(self) -> bool:
        """Retry gate for the donated-buffer hazard: on TPU the compiled
        programs donate the K/V caches (`donate_argnums`), and a launch
        that failed AFTER the dispatch consumed them leaves deleted
        arrays behind — re-passing those would raise, so the supervisor
        must fail over to the snapshot path instead of retrying. On CPU
        (donation off) and for failures raised BEFORE dispatch (fault
        injection, connect errors) the buffers stay alive and
        retries proceed."""
        probe = [c[0] for c in self._cache_lists() if c]
        return not any(getattr(a, "is_deleted", lambda: False)()
                       for a in probe)

    # ----------------------------------------- request tracing (ISSUE 10)
    # Every hook no-ops on `self.tracer is None` — the ONE check the
    # default (trace-off) hot path pays; nothing below it allocates.
    def _on_step_retry(self, label: str, attempt: int):
        self.metrics.on_step_retry()
        if self.tracer is not None:
            for rid in self._cur_rids:
                self.tracer.mark(rid, "retry", label=label,
                                 attempt=attempt,
                                 engine=self.metrics.name)

    def _tr_begin(self, req: Request):
        if self.tracer is None:
            return
        self.tracer.begin(req.request_id, engine=self.metrics.name,
                          prompt_len=len(req.prompt_ids),
                          max_new_tokens=req.max_new_tokens)

    def _tr_shed(self, req: Request):
        """Admission shed: the trace begins and ends at the door —
        sheds must be visible in the completed ring, not invisible."""
        if self.tracer is None:
            return
        self.tracer.begin(req.request_id, engine=self.metrics.name,
                          prompt_len=len(req.prompt_ids),
                          max_new_tokens=req.max_new_tokens)
        self.tracer.mark(req.request_id, "shed",
                         engine=self.metrics.name,
                         queue_depth=self.scheduler.queue_depth)
        self.tracer.finish(req.request_id, "shed")

    def _tr_admit(self, req: Request, resumed: bool):
        if self.tracer is None:
            return
        tr = self.tracer.get(req.request_id)
        if tr is None:
            return
        now = self.tracer.now_ns()
        tr.span("queue_wait", tr.t_queue, now, resumed=resumed)
        tr.mark("admitted", now, cached_tokens=req.cached_tokens,
                resumed=resumed, engine=self.metrics.name)

    def _tr_launch(self, rids, name: str, t0: int, t1=None, **args):
        """One span per PARTICIPATING request for a batched launch —
        the per-request timeline view of shared device work. The args
        are identical across the batch, so the record is built once
        (`span_many`) — the traced decode hot path stays cheap. The span
        ends at `t1`, or now."""
        if self.tracer is None:
            return
        self.tracer.span_many(rids, name, t0,
                              self.tracer.now_ns() if t1 is None else t1,
                              engine=self.metrics.name,
                              step=self._step_no, **args)

    def _tr_mark(self, rid: int, name: str, **args):
        if self.tracer is None:
            return
        self.tracer.mark(rid, name, engine=self.metrics.name, **args)

    def _tr_finish(self, rid: int, reason: str):
        if self.tracer is None:
            return
        self.tracer.finish(rid, reason)

    def _tr_preempt(self, req: Request):
        if self.tracer is None:
            return
        tr = self.tracer.get(req.request_id)
        if tr is None:
            return
        now = self.tracer.now_ns()
        tr.mark("preempted", now, engine=self.metrics.name)
        tr.t_queue = now     # the next admission's queue_wait anchor

    # ------------------------------------------------------------- intake
    def _now(self) -> float:
        return self._clock() + self._clock_skew

    def add_request(self, prompt_ids, max_new_tokens: int = 32,
                    eos_token_id: Optional[int] = None,
                    ttl_s: Optional[float] = None,
                    deadline: Optional[float] = None,
                    adapter: Optional[str] = None) -> int:
        """Queue one request. `ttl_s` (or an absolute engine-clock
        `deadline`) bounds its total lifetime: past it, the request is
        cancelled at the next iteration boundary whatever its state.
        Raises `EngineOverloaded` when the bounded waiting queue is full
        (admission control — shed at the door, never grow unbounded).

        `adapter` (ISSUE 15) names a LoRA adapter the registry must
        CURRENTLY hold — unknown/unloaded adapters shed typed
        (`AdapterNotLoaded`) at the door, never serve base weights by
        accident. An admitted request pins its adapter (registry
        refcount) until it reaches a terminal state, so LRU eviction
        can never take the weights out from under live work."""
        if self.failed:
            raise EngineFailure("engine has failed; resume from "
                                "last_snapshot", snapshot=self.last_snapshot)
        if adapter is not None:
            if self.lora is None:
                raise AdapterNotLoaded(
                    f"request names adapter {adapter!r} but this engine "
                    f"has no adapter registry (lora=None)",
                    adapter=adapter)
            if not self.lora.has(adapter):
                self.metrics.counters["adapter_rejects"] += 1
                raise AdapterNotLoaded(
                    f"adapter {adapter!r} is not loaded "
                    f"(loaded: {self.lora.adapter_names()})",
                    adapter=adapter)
        req = Request(prompt_ids, max_new_tokens, eos_token_id,
                      adapter=adapter)
        if len(req.prompt_ids) + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt {len(req.prompt_ids)} + max_new_tokens "
                f"{req.max_new_tokens} exceeds max_seq_len "
                f"{self.max_seq_len}")
        # NOTE: PR 1 also rejected requests whose post-preemption resume
        # (prompt + max_new - 1) outsized the largest prefill bucket.
        # Chunked prefill removed that failure mode: a resume of any
        # length within max_seq_len re-prefills in budget-sized chunks.
        if ttl_s is None and deadline is None and \
                self.default_ttl_s is not None:
            ttl_s = self.default_ttl_s
        if ttl_s is not None and deadline is not None:
            raise ValueError("pass ttl_s or deadline, not both")
        if ttl_s is not None:
            deadline = self._now() + float(ttl_s)
        req.deadline = deadline
        try:
            self.scheduler.add_request(req)
        except EngineOverloaded:
            self.metrics.on_shed()
            self._tr_shed(req)
            raise
        if adapter is not None:
            self.lora.acquire(adapter)     # pinned until terminal
            # versioned radix namespace: a reload of the same name
            # must never match KV cached under the replaced weights
            req.adapter_key = self.lora.namespace_of(adapter)
        self.requests[req.request_id] = req
        self.metrics.on_add(req.request_id)
        self._tr_begin(req)
        return req.request_id

    def abort(self, request_id: int) -> bool:
        """Client abort: the request is cancelled at the next iteration
        boundary in whatever state it is in (queued, chunk-prefilling,
        decoding, or preempted), its valid KV donated to the radix
        cache. Returns False when the request is unknown or already
        finished."""
        req = self.requests.get(request_id)
        if req is None or req.state is RequestState.FINISHED:
            return False
        req.aborted = True
        return True

    def has_work(self) -> bool:
        """Whether another `step()` has anything to do: a request is
        queued, prefilling or decoding. A decode launch enqueued ahead
        (`step()`) never outlives its rows (the step that ends the last
        of them drops it, and `vacate`, `shutdown` and a failure take it
        back), and what it holds is a token NOT yet returned, never one
        held back: there is nothing to flush."""
        return self.scheduler.has_work()

    # ---------------------------------------------------- TP placement
    def _place(self, arr, spec):
        """device_put `arr` onto the engine mesh per `spec` (replicated
        when spec is None); identity without a mesh. Specs whose rank
        does not fit the array (a reshaped/stacked buffer) fall back to
        replication — correctness never depends on placement, only
        memory footprint does."""
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P
        try:
            return jax.device_put(
                arr, NamedSharding(self.mesh,
                                   spec if spec is not None else P()))
        except Exception:   # noqa: BLE001 — rank/divisibility mismatch
            return jax.device_put(arr, NamedSharding(self.mesh, P()))

    def _trace_scope(self):
        """Context active around every program call: pins current_mesh()
        to the engine mesh so the mpu layers' GSPMD constraints (and the
        TP paged-attention route in models/llama.py) are live at trace
        time — without requiring fleet.init's process-global topology.
        A mesh-less engine pins mesh_scope(None), MASKING any ambient
        fleet.init mesh: otherwise a training process with mp>1 would
        leak its mesh into the serving trace and activate TP routing
        this engine never opted into (or validated divisibility for)."""
        from ..distributed.fleet.mpu import mesh_scope
        return mesh_scope(self.mesh)

    # ------------------------------------- multi-LoRA plumbing (ISSUE 15)
    def load_adapter(self, adapter, quant: Optional[str] = None) -> int:
        """Load a LoRAAdapter into the registry at runtime (no
        recompile — only page/table VALUES change). Returns the global
        launch slot. quant="int8" stores the payload quantized."""
        if self.lora is None:
            raise AdapterNotLoaded("engine has no adapter registry "
                                   "(construct with lora=...)")
        return self.lora.load(adapter, quant=quant)

    def unload_adapter(self, name: str):
        """Unload an IDLE adapter (typed AdapterBusy while live
        requests still pin it)."""
        if self.lora is None:
            raise AdapterNotLoaded("engine has no adapter registry "
                                   "(construct with lora=...)")
        self.lora.unload(name)

    def _lora_launch_args(self, reqs, B: int) -> tuple:
        """Per-launch lora program inputs: (row_slots (B,), *registry
        flat args) — empty when lora is off, so lora-less launch sites
        splat nothing. Padded batch rows map to global slot 0 (every
        bucket's null adapter -> exact zero delta)."""
        if self.lora is None:
            return ()
        rows = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            if r.adapter is not None:
                rows[i] = self.lora.slot_of(r.adapter)
        return (jnp.asarray(rows),) + self.lora.flat_args()

    def _lora_trace_scope(self, largs):
        """Scope entered INSIDE a traced program body, around the model
        call: builds the launch LoRAContext from the traced lora args
        and activates the projection hooks. Null context when off."""
        if self.lora is None or not largs:
            import contextlib
            return contextlib.nullcontext()
        from .lora.runtime import build_context, lora_scope
        return lora_scope(build_context(self.lora.layout, largs[1:],
                                        largs[0]))

    # ------------------------------------------------------ program cache
    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _geometry_signature(self) -> str:
        """Model/engine-geometry signature for the compile-cache
        fingerprint: an executable is only reusable when every array
        SHAPE it was lowered against matches, so the weight-state
        shapes/dtypes and the KV-pool geometry define validity (weight
        VALUES are call-time arguments, not baked in)."""
        import hashlib
        state = ";".join(f"{k}:{tuple(a.shape)}:{a.dtype}"
                         for k, a in sorted(self._state.items()))
        sig = (f"{type(self.model).__name__}|{state}|"
               f"pages={self.num_pages}x{self.page_size}|"
               f"layers={self.num_layers}")
        return hashlib.sha256(sig.encode()).hexdigest()[:16]

    @property
    def compile_cache(self):
        """The persistent CompileCache (None when not configured)."""
        return self.programs.disk

    def _sync_compile_cache_counters(self):
        """Mirror the CompileCache counters into the auto-exposed
        metrics counters (the Prometheus drift-test registry): the
        keys exist on every engine, zeroed when the cache is off."""
        cc = self.programs.disk
        if cc is not None:
            for k in ("hits", "misses", "rejects"):
                self.metrics.counters[f"compile_cache_{k}"] = \
                    cc.counters[k]

    def save_compile_cache(self) -> int:
        """Persist every launched program to the compile cache (no-op
        without one). Re-lowers AOT per new entry — a drain/shutdown-
        time cost; returns entries written. Workers call this on
        drain/SIGTERM so their successor reaches first-token without
        the compile storm (ISSUE 14)."""
        cc = self.programs.disk
        if cc is None:
            return 0
        written = cc.save_all(self.programs)
        self._sync_compile_cache_counters()
        return written

    def _get_program(self, key, builder):
        prog = self.programs.get(key, builder)
        self._sync_compile_cache_counters()
        return prog

    @property
    def num_compiled_programs(self) -> int:
        """Total compiled programs (all families); per-family counts via
        `program_counts()` (ISSUE 8)."""
        return self.programs.num_programs

    def program_counts(self) -> Dict[str, int]:
        """{family: programs compiled} for the chunk/decode/verify
        families through the unified ProgramCache."""
        return self.programs.counts()

    def comm_table(self) -> Dict[tuple, Optional[dict]]:
        """Per-program collective-traffic accounting (ISSUE 12), axis-
        attributed over THIS engine's mesh — the TP row-parallel psum
        on 'model' shows up on the decode rows. Compile-time-only cost,
        like cost_table()."""
        return self.programs.comm_table(mesh=self.mesh)

    def max_program_count(self, family: Optional[str] = None) -> int:
        """The bucket-grid bound the recompile counter can never exceed
        — one family's grid, or (default) the sum over all families.
        With a proposer the ("verify", B, K, P) grid joins it: K is a
        program-cache key axis exactly like B and P, so speculative
        decoding multiplies the decode-side bound by len(spec_buckets)
        instead of compiling per draft length (SERVING.md documents the
        bound next to the PR-1 bucket-grid note). The mesh shape also
        rides every key, but an engine owns ONE mesh, so its bound is
        the grid for that single mesh shape."""
        return self.programs.max_count(family)

    # --------------------------------------------- paged-cache plumbing
    @staticmethod
    def _paged_views(kcs, vcs, kss, vss):
        """Per-layer cache tuples for the model's paged entry, one
        Tensor for each array of its cache entry: (k, v) for Llama's
        full-width KV, (k, v, k_scale, v_scale) for int8 (the model
        branches on tuple arity, ISSUE 6), (pool,) for a latent cache."""
        lists = [c for c in (kcs, vcs, kss, vss) if c]
        return [tuple(Tensor(c[l]) for c in lists)
                for l in range(len(kcs))]

    @staticmethod
    def _split_views(caches):
        """Inverse of _paged_views: four flat array lists (those the
        entry does not fill empty) — the uniform program return shape."""
        n = len(caches[0]) if caches else 0
        return tuple([c[i]._data for c in caches] if i < n else []
                     for i in range(4))

    def _cache_lists(self):
        return (self._k_caches, self._v_caches, self._k_scales,
                self._v_scales)

    def _store_caches(self, kcs, vcs, kss, vss):
        self._k_caches, self._v_caches = kcs, vcs
        self._k_scales, self._v_scales = kss, vss

    def _count_model(self, counts):
        """Add a launch's model counters (fetched with its tokens) to
        the metrics counters of their names."""
        if self._model_counters:
            got = {name: int(n) for name, n in
                   zip(self._model_counters, np.asarray(counts))}
            for name, n in got.items():
                self.metrics.counters[name] += n
            # in a trace, THIS launch's counts on the profiler's clock
            # beside its device ops: a reader sums a slice's own
            with profiler.RecordEvent("serving.model_counters", **got):
                pass

    # ---------------------------------------- the one paged program frame
    def _paged_program(self, body):
        """The frame of every paged program around one family's
        `body(st, paged, *inputs) -> (*outputs, caches)`, which calls the
        model (PAGED_ENTRY over a span, or `decode_multi`) on the state
        wrapped in Tensors and the per-layer cache views and makes of
        the logits what is the family's own. The inputs the body names
        are followed by the launch's LoRA arguments, if it passes any
        (`_lora_launch_args`); their scope is open over the WHOLE body,
        so under a scan the gathered slot stacks are loop constants and
        the paged gather runs once a LAUNCH, not once a decode step.
        `program(state, kcs, vcs, kss, vss, *inputs) -> (*outputs, kcs,
        vcs, kss, vss)`, jitted with the four cache lists donated."""
        views, split = self._paged_views, self._split_views
        lora_open = self._lora_trace_scope
        n_in = len(inspect.signature(body).parameters) - 2

        def program(state, kcs, vcs, kss, vss, *inputs):
            st = {k: Tensor(v) for k, v in state.items()}
            paged = views(kcs, vcs, kss, vss)
            with lora_open(inputs[n_in:]):
                *outs, caches = body(st, paged, *inputs[:n_in])
            return tuple(outs) + split(caches)

        # tpu-lint: cache-key-ok (donation is backend-constant per process)
        return jax.jit(program, donate_argnums=self._donate)

    # ------------------------------------------------ the one launch path
    def _launcher(self, family: str, dims: tuple, builder, rids, inputs,
                  key, largs=()):
        """The one launch path of the four program families. Called in
        the launch's `serving.build_inputs` span, it looks the program
        up under `(family,) + dims + self._qkey`, records what the retry
        hook (`_cur_rids`) and the step record read, and returns the
        supervised launch: called, that fires the family's fault point
        and runs the program in the family's launch span (`_LAUNCHES`)
        under the poison scope naming `rids`, `no_grad` and the engine's
        mesh; its result is the program's, still on the device, the four
        cache lists last. Between the two the caller stamps its clocks;
        after, it fetches and keeps its books in its family's order
        (PERF.md section 3). `inputs` are the host arrays after the
        caches, put on the device on every attempt; `key` is drawn ONCE,
        by the caller, so a transient-failure retry re-runs the identical
        program (bit-identical tokens) and burns no key per attempt."""
        name, letters, fault = _LAUNCHES[family]
        prog = self._get_program((family,) + dims + self._qkey, builder)
        self._cur_rids = tuple(rids)
        self._step_ev["programs"].append(":".join(
            [family] + [f"{c}{d}" for c, d in zip(letters, dims)]))
        who = f"req={rids[0]}" if family == "chunk" else f"reqs={rids}"

        def launch():
            faults.fire(fault)
            with profiler.RecordEvent("serving." + name,
                                      bucket=list(dims)), \
                    poison_scope(f"serving.{name}[{who}]"), no_grad(), \
                    self._trace_scope():
                return prog(self._state, *self._cache_lists(),
                            *[jnp.asarray(a) for a in inputs], key, *largs)

        return lambda: self.supervisor.run(launch, label=name)

    def _decode_batch(self, reqs: List[Request]):
        """What the three decode-side families' launches share: the
        batch and block-table buckets, the PAD_PAGE-filled block table,
        the request ids, and the LoRA launch arguments with the launch's
        adapter-mix sample. Returns (B, P, bt (B, P), rids, largs)."""
        B = _bucket_for(len(reqs), self.batch_buckets)
        P = _bucket_for(max(len(r.seq.pages) for r in reqs),
                        self.pages_buckets)
        seqs = [r.seq for r in reqs]

        def table(group):
            bt = np.full((B, P), PAD_PAGE, np.int32)
            bt[:len(reqs)] = self.allocator.block_table(seqs, P, group)
            return bt

        bt = table(0)
        if self.allocator.windows:
            # a table a layer group, at the same positions: (G, B, P)
            bt = np.stack([bt] + [
                table(g) for g in range(1, 1 + len(self.allocator.windows))])
        largs = self._lora_launch_args(reqs, B)
        if self.lora is not None:
            self.metrics.on_adapter_mix(
                len({r.adapter for r in reqs if r.adapter is not None}))
        return B, P, bt, [r.request_id for r in reqs], largs

    # ----------------------------------------------------- prefill chunks
    def _build_chunk(self, S: int, P: int):
        """One padded prompt CHUNK -> paged cache + sampled token (the
        token is only consumed when the chunk is the prompt's last)."""
        # tpu-lint: cache-key-ok (per-engine cache; disk tier keys geometry)
        model = self.model
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p

        def body(st, paged, ids, cache_len, live, bt, key):
            logits, caches, counts = functional_call(
                model, st, Tensor(ids), paged, Tensor(bt),
                PagedSpan("prefill", Tensor(cache_len), Tensor(live)),
                method=PAGED_ENTRY)
            last = logits._data[0, 0]   # head ran at the chunk end only
            # in-graph NaN detection (the jit counterpart of the eager
            # dispatch NaN hook): NaN/Inf anywhere in the network flows
            # into the chunk-end logits, so one reduction covers the step
            ok = jnp.all(jnp.isfinite(last))
            tok = _sample_arr(last[None], key, temperature, top_k, top_p)[0]
            return tok, ok, counts, caches

        return self._paged_program(body)

    def _run_chunk(self, chunk):
        req = chunk.request
        with profiler.RecordEvent("serving.build_inputs"):
            ids = req.resume_ids[chunk.start:chunk.start + chunk.length]
            S = _bucket_for(chunk.length, self.prefill_buckets)
            P = _bucket_for(
                self.allocator.pages_needed(chunk.start + chunk.length),
                self.pages_buckets)
            bt = np.full((P,), PAD_PAGE, np.int32)
            npages = min(len(req.seq.pages), P)
            bt[:npages] = req.seq.pages[:npages]
            if self.allocator.windows:
                bt = np.stack([bt] + [
                    self.allocator.block_table([req.seq], P, g)[0]
                    for g in range(1, 1 + len(self.allocator.windows))])
            padded = np.zeros((1, S), np.int32)
            padded[0, :chunk.length] = ids
            launch = self._launcher(
                "chunk", (S, P), lambda: self._build_chunk(S, P),
                [req.request_id],
                (padded, np.int32(chunk.start), np.int32(chunk.length), bt),
                # only a prompt's last chunk consumes its token
                self._next_key() if chunk.is_last else self._null_key,
                self._lora_launch_args([req], 1))

        t_tr = self.tracer.now_ns() if self.tracer is not None else 0
        tok, ok, counts, *caches = launch()
        with profiler.RecordEvent("serving.bookkeeping"):
            self._tr_launch((req.request_id,), "prefill_chunk", t_tr,
                            start=chunk.start, length=chunk.length,
                            bucket=[S, P], last=chunk.is_last)
            self._store_caches(*caches)
            if faults.fire(FAULT_NAN) is not None:
                ok = False
            self.metrics.on_prefill(chunk.length)
            # the chunk wrote its own tokens' K/V and its attention
            # gathered the whole live prefix (cached tokens + this chunk)
            # per layer
            self.metrics.on_kv_bytes(
                written=chunk.length * self.kv_bytes_per_token,
                read=(chunk.start + chunk.length)
                * self.kv_bytes_per_token)
        with profiler.RecordEvent("serving.fetch"):
            ok = bool(ok)              # host fetch = the honest sync
            if ok and chunk.is_last:
                tok = int(tok)
            self._count_model(counts)
        return tok, ok

    # ----------------------------------------------------------- decode
    def _build_decode(self, B: int, P: int):
        """One batched token step over the paged caches."""
        # tpu-lint: cache-key-ok (per-engine cache; disk tier keys geometry)
        model = self.model
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p

        def body(st, paged, ids, bt, sl, key):
            logits, caches, counts = functional_call(
                model, st, Tensor(ids), paged, Tensor(bt),
                PagedSpan("decode", Tensor(sl)), method=PAGED_ENTRY)
            rows = logits._data[:, 0, :]
            # per-row finiteness: rows are independent (SERVING.md), so a
            # poisoned request flags ONLY its own row — the quarantine
            # granularity ("fail one request, not the engine")
            ok = jnp.all(jnp.isfinite(rows), axis=-1)
            toks = _sample_arr(rows, key, temperature, top_k, top_p)
            return toks, ok, counts, caches

        return self._paged_program(body)

    def _enqueue_decode(self, reqs: List[Request],
                        prev: Optional[_DecodeLaunch] = None):
        """Enqueue one decode launch over `reqs`, each with the slot of
        its input token reserved, and return it in flight: nothing is
        waited for. With `prev`, the launch before it whose tokens have
        not been fetched, a row that rode in `prev` takes its input id
        from `prev`'s tokens where they are, on the device; any other
        row's last token is on the host. The caches chain from launch to
        launch as device futures, so they are stored here."""
        with profiler.RecordEvent("serving.build_inputs"):
            B, P, bt, rids, largs = self._decode_batch(reqs)
            fresh = np.zeros((B,), np.int32)
            src = np.full((B,), -1, np.int32)
            sl = np.zeros((B,), np.int32)
            row = {} if prev is None else prev.row
            for i, r in enumerate(reqs):
                sl[i] = r.seq.num_tokens
                if r in row:
                    src[i] = row[r]
                else:
                    fresh[i] = r.output_ids[-1]
            ids = fresh[:, None] if prev is None \
                else _ids_after(prev.out[0], src, fresh)
            launch = self._launcher(
                "decode", (B, P), lambda: self._build_decode(B, P), rids,
                (ids, bt, sl), self._next_key(), largs)

        t_tr = t0 = None
        if prev is None:
            t_tr = self.tracer.now_ns() if self.tracer is not None else 0
            t0 = _perf_counter()
        toks, oks, counts, *caches = launch()
        self._store_caches(*caches)
        # the label goes to the record of the step that FETCHES the launch
        return _DecodeLaunch(reqs, (B, P), sl, (toks, oks, counts), t0, t_tr,
                             self._step_ev["programs"].pop())

    def _fetch_decode(self, flight: _DecodeLaunch, live: List[Request]):
        """Wait for `flight` and keep its books over `live`, those of its
        rows that are still decoding: a row that finished while the
        launch was in flight (its end was read from the token before, it
        was aborted, it expired) counts nowhere. Returns (request, token,
        finite) for each of `live`. ONE fetch brings tokens, flags and model
        counters; the TPOT sample and the requests' launch spans end
        there, and begin where the launch was enqueued or, for a launch
        enqueued ahead, where the last launch's tokens arrived: the time
        these tokens took to follow those."""
        with profiler.RecordEvent("serving.fetch"):
            # host fetch = the honest sync
            toks, oks, counts = jax.device_get(flight.out)
            self._count_model(counts)
            t1 = _perf_counter()
            t1_tr = self.tracer.now_ns() if self.tracer is not None else 0
            t0, t0_tr = self._fetched if flight.ahead \
                else (flight.t0, flight.t_tr)
            self._last_launch_s = t1 - t0
            self._fetched = (t1, t1_tr)
            oks = np.array(oks[:len(flight.reqs)])
        with profiler.RecordEvent("serving.bookkeeping"):
            B, P = flight.dims
            self._step_ev["programs"].append(flight.label)
            self._step_ev["decode_k"] = 1
            self._step_ev["decode_ahead"] = flight.ahead
            self._tr_launch([r.request_id for r in live], "decode_step",
                            t0_tr, t1_tr, batch=len(live), bucket=[B, P],
                            k=1)
            self._fire_nan(oks, flight.reqs)
            context = 0
            for r in live:
                # the launch wrote the K/V of the row's input token
                r.num_computed = int(flight.sl[flight.row[r]])
                context += r.num_computed
            # bytes-moved accounting: the launch wrote one token per live
            # row and the attention kernel read every live token's K/V
            self.metrics.on_kv_bytes(
                written=len(live) * self.kv_bytes_per_token,
                read=context * self.kv_bytes_per_token)
            self.metrics.on_decode(len(live))
            self._count_gather(flight.sl, P)
        return [(r, toks[flight.row[r]], oks[flight.row[r]]) for r in live]

    def _fire_nan(self, oks, reqs):
        """The nan_logits fault point, once a decode-side launch: the
        payload's rows of `oks` go down. A payload is callable(reqs) ->
        rows, True/'all' -> every row, an int or a list of ints -> those
        rows (out-of-range ignored)."""
        poison = faults.fire(FAULT_NAN)
        if poison is None:
            return
        if callable(poison):
            rows = poison(reqs)
        elif poison is True or poison == "all":
            rows = range(len(reqs))
        elif isinstance(poison, int):
            rows = [poison]
        else:
            rows = poison
        for i in map(int, rows):
            if 0 <= i < len(reqs):
                oks[i] = False

    # --------------------------------------- multi-step decode (ISSUE 13)
    def _build_multi_decode(self, B: int, K: int, P: int):
        """K decode iterations in ONE compiled launch: a device-side
        scan over the decode body with in-graph sampling (per-step keys
        folded from the one pre-drawn launch key), per-step paged cache
        writes through the loop carry, and per-row freeze masks
        (EOS / per-row step cap / non-finite logits). The host fetches
        only (tokens (B, K), emitted counts, finiteness flags) — one
        host round trip buys up to K tokens per row."""
        # tpu-lint: cache-key-ok (per-engine cache; disk tier keys geometry)
        model = self.model
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p

        def body(st, paged, ids, bt, sl, caps, eos, key):
            toks, n_emit, ok, caches, counts = functional_call(
                model, st, Tensor(ids), paged, Tensor(bt), Tensor(sl),
                Tensor(caps), Tensor(eos), key,
                method=decode_multi, k_steps=K,
                temperature=temperature, top_k=top_k, top_p=top_p)
            return toks._data, n_emit._data, ok._data, counts, caches

        return self._paged_program(body)

    def _run_multi_decode(self, reqs: List[Request], caps: List[int],
                          K: int):
        """One supervised ("multi_decode", B, K, P) launch. `reqs[i]`'s
        sequence is already extended by caps[i] - 1 slots; returns
        (toks (B, K), n_emit (B,), oks (B,), launch seconds)."""
        with profiler.RecordEvent("serving.build_inputs"):
            B, P, bt, rids, largs = self._decode_batch(reqs)
            ids = np.zeros((B,), np.int32)
            sl = np.zeros((B,), np.int32)
            cp = np.zeros((B,), np.int32)
            eos = np.full((B,), -1, np.int32)
            for i, (r, c) in enumerate(zip(reqs, caps)):
                ids[i] = r.output_ids[-1]
                # seq_lens counts through the FIRST input token (the
                # decode span's convention); the extension slots grew
                # num_tokens past it, so subtract them back out
                sl[i] = r.seq.num_tokens - (c - 1)
                cp[i] = c
                if r.eos_token_id is not None:
                    eos[i] = r.eos_token_id
            launch = self._launcher(
                "multi_decode", (B, K, P),
                lambda: self._build_multi_decode(B, K, P), rids,
                (ids, bt, sl, cp, eos), self._next_key(), largs)
            self._step_ev["decode_k"] = K

        t_tr = self.tracer.now_ns() if self.tracer is not None else 0
        t0 = _perf_counter()
        toks, n_emit, oks, counts, *caches = launch()
        # the host fetch is the sync: convert
        # BEFORE stamping the launch time so TPOT covers device work
        with profiler.RecordEvent("serving.fetch"):
            toks = np.asarray(toks)
            self._count_model(counts)
            n_emit = np.asarray(n_emit).astype(int)
            oks = np.asarray(oks)[:len(reqs)].copy()
        dt = _perf_counter() - t0
        with profiler.RecordEvent("serving.bookkeeping"):
            self._tr_launch(rids, "multi_decode_step", t_tr,
                            batch=len(reqs), bucket=[B, K, P], k=K)
            self._store_caches(*caches)
            # bytes-moved accounting: every live row writes one token's
            # K/V per step (frozen steps idempotently rewrite the last
            # token), and each step's attention reads the row's
            # then-current prefix (frozen rows re-read at their frozen
            # length)
            base_lens = sl[:len(reqs)].astype(int)
            reads = sum(int(b0) * K + sum(min(j, int(e)) for j in range(K))
                        for b0, e in zip(base_lens, n_emit[:len(reqs)]))
            self.metrics.on_kv_bytes(
                written=len(reqs) * K * self.kv_bytes_per_token,
                read=reads * self.kv_bytes_per_token)
            for r in reqs:
                r.num_computed = r.seq.num_tokens
            self._fire_nan(oks, reqs)
        return toks, n_emit, oks, dt

    def _multi_decode_step(self, decodes: List[Request], emitted):
        """The multi-step replacement for the plain decode launch:
        extend each sequence by up to K-1 slots -> ONE scan launch ->
        emit each row's tokens up to its in-graph freeze point -> roll
        unused slots back.

        Failure semantics mirror the decode step: transients retried by
        the supervisor (writes are idempotent, the RNG key pre-drawn);
        a row whose per-launch finiteness flag is down is quarantined
        alone and delivers NO token from the poisoned launch (per-LAUNCH
        quarantine granularity — SERVING.md); unattributed poison rolls
        the extension slots back and isolates via solo PLAIN decode
        launches; anything else drains to a snapshot. Abort/TTL are
        honored at the next K-boundary with this launch's tokens
        delivered."""
        caps = []
        for req in decodes:
            want = min(self.decode_steps, req.remaining_new_tokens())
            granted, copies = self._extend_slots(req, want - 1)
            if granted < want - 1:
                self.metrics.counters["multi_decode_slot_shortfall"] += \
                    (want - 1) - granted
            if copies:
                self._apply_copies(copies)
            caps.append(1 + granted)
        K = _bucket_for(max(caps), self.multi_buckets)
        isolated = False
        dt = None
        try:
            toks, n_emit, oks, dt = self._run_multi_decode(
                decodes, caps, K)
        except Exception as exc:   # noqa: BLE001
            if classify_failure(exc) != POISON:
                self._fail(exc)
            # unattributed poison: drop the extension slots (their K/V
            # is suspect) and isolate with solo plain-decode launches
            for req, cap in zip(decodes, caps):
                if cap > 1:
                    self.allocator.truncate_sequence(
                        req.seq, req.seq.num_tokens - (cap - 1))
            toks1, oks = self._isolate_poisoned(decodes)
            toks = np.full((len(decodes), 1), -1, np.int64)
            toks[:, 0] = toks1
            n_emit = np.ones((len(decodes),), int)
            caps = [1] * len(decodes)
            isolated = True
        with profiler.RecordEvent("serving.emit"):
            total_emitted = 0
            for i, req in enumerate(decodes):
                base = req.seq.num_tokens - (caps[i] - 1)  # through input tok
                if not oks[i]:
                    # per-launch quarantine: pages (extension slots
                    # included) freed WITHOUT donation, no token delivered
                    self._quarantine(req)
                    continue
                e = int(n_emit[i])
                reason = None
                n_done = 0
                for j in range(e):
                    reason = self._emit(req, int(toks[i, j]), emitted)
                    n_done += 1
                    if reason is not None:
                        break
                # valid K/V: the input token + the emitted tokens actually
                # CONSUMED as later in-graph inputs (n_done - 1 of them);
                # unused extension slots roll back so donation/resume never
                # sees past-freeze garbage
                valid = base + max(n_done, 1) - 1
                if req.seq.num_tokens > valid:
                    self.allocator.truncate_sequence(req.seq, valid)
                req.num_computed = valid
                total_emitted += n_done
                if reason is not None:
                    self.scheduler.finish(req, reason)
                    self._on_finished(req)
            if not isolated:
                self.metrics.on_decode(total_emitted)
                self.metrics.on_decode_launch(K, len(decodes), total_emitted,
                                              dt)
            else:
                # the isolation path's solo launches counted decode_tokens
                # in _fetch_decode; record their row count too (one row
                # per solo launch, k=1, no timing) or the
                # tokens-per-launch ratio would keep a numerator with no
                # denominator and read ABOVE its true value after any
                # degraded event
                self.metrics.on_decode_launch(1, len(decodes), 0, None)

    # ------------------------------------------- speculative verify (ISSUE 5)
    def _build_verify(self, B: int, K: int, P: int):
        """One speculative VERIFY launch: scores each row's
        [last emitted token, draft_1..draft_K] in one pass over the
        paged caches and resolves acceptance IN-GRAPH, so the host
        fetches only (tokens, accepted counts, finiteness flags).

        Acceptance implements rejection sampling for a DETERMINISTIC
        (one-hot) proposal — both shipped proposers draft greedily:
        * temperature == 0: longest prefix with argmax(prev logits) ==
          draft, then the argmax correction/bonus token. Emitted tokens
          are exactly the argmaxes plain decode would emit, which is
          the greedy bit-identity contract.
        * temperature > 0: draft d at position j accepts iff
          u_j < p_j(d) (p = the SAME filtered/tempered distribution
          `_sample_arr` uses); a rejected position samples the
          renormalized remainder of p with d removed — exact residual
          for a one-hot proposal, so the output distribution equals
          plain sampled decode's. All randomness derives from the one
          pre-drawn key, so StepSupervisor retries stay bit-identical.
        """
        S = K + 1
        # tpu-lint: cache-key-ok (per-engine cache; disk tier keys geometry)
        model = self.model
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p

        def body(st, paged, ids, bt, sl, dl, key):
            logits, caches, counts = functional_call(
                model, st, Tensor(ids), paged, Tensor(bt),
                PagedSpan("verify", Tensor(sl), Tensor(dl)),
                method=PAGED_ENTRY)
            lg = logits._data                            # (B, S, V)
            jpos = jnp.arange(S, dtype=jnp.int32)[None, :]
            live_q = jpos <= dl[:, None]                 # (B, S)
            # per-row finiteness over LIVE positions only (padding rows
            # run on clamped positions; only real work may quarantine)
            fin = jnp.all(jnp.isfinite(lg), axis=-1)
            ok = jnp.all(jnp.where(live_q, fin, True), axis=-1)
            drafts = ids[:, 1:]                          # (B, K)
            # position j's logits score draft j+1: live iff j < dl
            has_draft = jpos[:, :K] < dl[:, None]
            idsn = jnp.concatenate(
                [drafts, jnp.zeros((B, 1), ids.dtype)], axis=1)  # (B, S)
            if temperature <= 0.0:
                pred = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                acc = jnp.logical_and(pred[:, :K] == drafts, has_draft)
                n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32),
                                            axis=1), axis=1)
                toks = jnp.where(jpos < n_acc[:, None], idsn, pred)
            else:
                p = jax.nn.softmax(
                    _filter_logits(lg, temperature, top_k, top_p),
                    axis=-1)
                k_u, k_r = jax.random.split(key)
                u = jax.random.uniform(k_u, (B, K))
                p_draft = jnp.take_along_axis(
                    p[:, :K], drafts[..., None].astype(jnp.int32),
                    axis=-1)[..., 0]
                acc = jnp.logical_and(u < p_draft, has_draft)
                n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32),
                                            axis=1), axis=1)
                # residual at a draft position = p with the draft token
                # zeroed + renormalized (the rejected position has
                # p(d) < u <= 1, so the remainder has positive mass);
                # the bonus position (j == dl) samples p itself
                has_draft_s = jpos < dl[:, None]         # (B, S)
                onehot = jax.nn.one_hot(idsn.astype(jnp.int32),
                                        p.shape[-1], dtype=p.dtype)
                res = p * (1.0 - jnp.where(has_draft_s[..., None],
                                           onehot, 0.0))
                res = res / jnp.maximum(
                    jnp.sum(res, axis=-1, keepdims=True), 1e-30)
                sampled = jax.random.categorical(
                    k_r, jnp.log(res + 1e-30), axis=-1).astype(jnp.int32)
                toks = jnp.where(jpos < n_acc[:, None], idsn, sampled)
            return toks, n_acc, ok, counts, caches

        return self._paged_program(body)

    def _extend_slots(self, req: Request, want: int):
        """Grow the request's sequence by up to `want` token slots (the
        scheduler already reserved this launch's input-token slot).
        On pool exhaustion the reclamation ladder stops at its FIRST
        rung — radix LRU eviction of zero-active-ref cached prefixes
        (otherwise a long-lived server whose pool has filled with
        donated prefixes, the normal steady state, would drop every
        extra slot and silently lose the multi-token win) — but NEVER
        preempts: the extra slots are advisory (draft tokens / extra
        decode steps), and evicting live work to make room for them
        would invert the priority order. Degrades, never fails:
        `append_token` is atomic, so a dry pool just grants fewer
        slots — zero means the launch degenerates to a single step.
        Returns (granted, CoW copies due)."""
        base = req.seq.num_tokens
        copies, granted = [], 0
        for _ in range(want):
            try:
                copies.extend(self.allocator.append_token(req.seq))
            except BlocksExhausted:
                if not self.scheduler._reclaim(1):
                    break
                try:
                    copies.extend(self.allocator.append_token(req.seq))
                except BlocksExhausted:
                    break
            granted += 1
        assert req.seq.num_tokens == base + granted
        return granted, copies

    def _extend_for_drafts(self, req: Request, draft: List[int]):
        """Spec-decode slot extension: grow by up to len(draft) slots
        via `_extend_slots`, shortening the draft to what the pool
        granted. Returns (granted draft list, CoW copies due)."""
        granted, copies = self._extend_slots(req, len(draft))
        if granted < len(draft):
            self.metrics.on_spec_draft_oom(len(draft) - granted)
        del draft[granted:]
        return draft, copies

    def _run_verify(self, reqs: List[Request], drafts: List[List[int]]):
        """One supervised ("verify", B, K, P) launch. `reqs[i]`'s
        sequence is already extended by len(drafts[i]); returns
        (toks (B, K+1), n_acc (B,), oks (B,))."""
        with profiler.RecordEvent("serving.build_inputs"):
            # lora and a proposer are a refused pair: no LoRA arguments
            B, P, bt, rids, _ = self._decode_batch(reqs)
            K = _bucket_for(max((len(d) for d in drafts), default=0) or 1,
                            self.spec_buckets)
            ids = np.zeros((B, K + 1), np.int32)
            sl = np.zeros((B,), np.int32)
            dl = np.zeros((B,), np.int32)
            for i, (r, d) in enumerate(zip(reqs, drafts)):
                ids[i, 0] = r.output_ids[-1]
                ids[i, 1:1 + len(d)] = d
                dl[i] = len(d)
                # seq_lens counts through the FIRST input token (the
                # decode span's convention); the drafts extended
                # num_tokens past it, so subtract them back out
                sl[i] = r.seq.num_tokens - len(d)
            launch = self._launcher(
                "verify", (B, K, P), lambda: self._build_verify(B, K, P),
                rids, (ids, bt, sl, dl), self._next_key())
            # tokens-per-launch context for the step record: a verify
            # launch can emit up to K drafts + 1 correction/bonus per row
            self._step_ev["decode_k"] = K + 1

        t_tr = self.tracer.now_ns() if self.tracer is not None else 0
        toks, n_acc, oks, counts, *caches = launch()
        with profiler.RecordEvent("serving.bookkeeping"):
            if self.tracer is not None:
                t1 = self.tracer.now_ns()
                for rid, d in zip(rids, drafts):
                    self.tracer.span(rid, "verify_step", t_tr, t1,
                                     engine=self.metrics.name,
                                     step=self._step_no,
                                     batch=len(reqs), drafted=len(d),
                                     bucket=[B, K, P])
            self._store_caches(*caches)
            self.metrics.on_kv_bytes(
                written=int(sum(1 + len(d) for d in drafts))
                * self.kv_bytes_per_token,
                read=sum(r.seq.num_tokens for r in reqs)
                * self.kv_bytes_per_token)
        with profiler.RecordEvent("serving.fetch"):
            oks = np.asarray(oks)[:len(reqs)].copy()
            toks = np.asarray(toks)
            n_acc = np.asarray(n_acc).astype(int)
            self._count_model(counts)
        self._fire_nan(oks, reqs)
        return toks, n_acc, oks

    def _spec_decode_step(self, decodes: List[Request], emitted):
        """The speculative replacement for the plain decode launch:
        propose -> extend KV -> ONE verify launch -> emit the accepted
        prefix + correction/bonus -> roll rejected drafts' pages back.

        Failure semantics mirror the decode step: transients retried by
        the supervisor (the verify write is idempotent and the RNG key
        pre-drawn); per-row poison quarantines alone; unattributed
        poison rolls every draft back and isolates via solo PLAIN
        decode launches (the degraded path already documented for
        decode); anything else drains to a snapshot."""
        # drafts are advisory and capped so the emitted tokens can never
        # overshoot max_new_tokens: a request with r remaining tokens
        # can use at most r - 1 accepted drafts (+1 correction/bonus)
        proposals = self.proposer.propose(decodes, self.spec_k)
        storm = faults.fire(FAULT_DRAFT)
        if storm is not None:
            proposals = (storm(decodes, self.spec_k) if callable(storm)
                         else [[(i * 7 + j * 13 + 1) %
                                max(2, self.cfg.vocab_size)
                                for j in range(self.spec_k)]
                               for i in range(len(decodes))])
        drafts = []
        for req, prop in zip(decodes, proposals):
            cap = max(0, min(self.spec_k, req.remaining_new_tokens() - 1))
            d = [int(t) for t in list(prop)[:cap]]
            d, copies = self._extend_for_drafts(req, d)
            if copies:
                self._apply_copies(copies)
            drafts.append(d)

        isolated = False
        try:
            toks, n_accs, oks = self._run_verify(decodes, drafts)
        except Exception as exc:   # noqa: BLE001
            if classify_failure(exc) != POISON:
                self._fail(exc)
            # unattributed poison: drop every draft (their K/V is
            # suspect) and isolate with solo plain-decode launches
            for req, d in zip(decodes, drafts):
                if d:
                    self.allocator.truncate_sequence(
                        req.seq, req.seq.num_tokens - len(d))
            # the rolled-back drafts are real rollback work even though
            # no verify step completed — count them without minting a
            # phantom spec step
            self.metrics.counters["spec_rollback_tokens"] += sum(
                len(d) for d in drafts)
            toks1, oks = self._isolate_poisoned(decodes)
            toks = np.zeros((len(decodes), 2), np.int64)
            toks[:, 0] = toks1
            n_accs = np.zeros((len(decodes),), int)
            drafts = [[] for _ in decodes]
            isolated = True   # solo launches counted their own tokens

        with profiler.RecordEvent("serving.emit"):
            total_drafted = total_accepted = total_emitted = total_rb = 0
            rows = 0
            for i, req in enumerate(decodes):
                d = drafts[i]
                base = req.seq.num_tokens - len(d)   # tokens through input
                if not oks[i]:
                    # quarantine frees the whole sequence (no donation) —
                    # rejected-draft pages go with it
                    self._quarantine(req)
                    continue
                n_emit = 0
                reason = None
                for j in range(int(n_accs[i]) + 1):
                    reason = self._emit(req, int(toks[i, j]), emitted)
                    n_emit += 1
                    if reason is not None:
                        break
                # valid K/V: the input token + the accepted drafts actually
                # CONSUMED (n_emit - 1 of them); everything past it rolls
                # back so donation/resume never sees speculative garbage
                valid = base + n_emit - 1
                rolled = req.seq.num_tokens - valid
                if rolled:
                    self.allocator.truncate_sequence(req.seq, valid)
                req.num_computed = valid
                total_drafted += len(d)
                total_accepted += n_emit - 1
                total_emitted += n_emit
                total_rb += rolled
                rows += 1
                if reason is not None:
                    self.scheduler.finish(req, reason)
                    self._on_finished(req)
            # decode_tokens counts tokens EMITTED by decode-side launches
            # (1/request for plain decode) so tokens/s stays honest. The
            # isolation path counted its own solo launches and verified
            # nothing — recording a spec step for it would drag
            # spec_tokens_per_step below its true value.
            if not isolated:
                self.metrics.on_decode(total_emitted)
                self.metrics.on_spec_step(total_drafted, total_accepted,
                                          total_emitted, total_rb, rows)

    # ---------------------------------------------------- CoW page copies
    def _apply_copies(self, copies):
        """Device-side CoW: copy a page's rows to a fresh page. For
        int8 KV the per-slot scale rows are part of the page's identity
        and copy WITH it — a fork that only copied values would
        dequantize the new page with the old (soon divergent) scales."""
        for src, dst in copies:
            for pool in self._cache_lists():
                for l in range(len(pool)):
                    pool[l] = pool[l].at[dst].set(pool[l][src])

    # ------------------------------------- tiered KV page I/O (ISSUE 17)
    def _payload_order(self):
        """(pool, layer) of each array of a page's payload, in the
        codec's order: the value arrays layer by layer (k row, v row),
        then the int8 scale rows the same way."""
        k, v, ks, vs = self._cache_lists()
        return [(pool, l) for a, b in ((k, v), (ks, vs))
                for l in range(len(a)) for pool in (a, b) if pool]

    def _gather_page_payload(self, pid: int) -> bytes:
        """One device page's bytes as an encoded payload: k row, v row
        per layer, then the int8 scale rows when the cache is
        quantized. A real device->host fetch per array (np.asarray
        synchronizes). The byte round trip is
        exact — np.asarray and .at[].set move raw rows, so a promoted
        page is bit-identical to the page that was demoted."""
        return encode_page_payload(
            [np.asarray(pool[l][pid]) for pool, l in self._payload_order()])

    def _scatter_page_payload(self, pid: int, arrays) -> None:
        """Inverse of `_gather_page_payload` onto device page `pid`:
        enqueues the per-layer `.at[pid].set(...)` writes and returns
        WITHOUT a host sync — the copies overlap whatever launch comes
        next, and the device stream orders them before any kernel that
        reads the page. Raises HostPageCorrupt on an array-count
        mismatch (a decoded payload from a different engine geometry
        must never partially land)."""
        order = self._payload_order()
        if len(arrays) != len(order):
            raise HostPageCorrupt(
                f"page payload has {len(arrays)} arrays; this engine "
                f"needs {len(order)}")
        for (pool, l), a in zip(order, arrays):
            pool[l] = pool[l].at[pid].set(jnp.asarray(a))

    def _spill_gauges(self) -> dict:
        """update_gauges kwargs for the radix eviction rungs and the
        host spill tier — empty fields stay None-untouched, so a
        cache-off or spill-off engine never zeroes counters it does
        not own. Called at BOTH gauge sites (step and vacate)."""
        out = {}
        if self.radix is not None:
            out.update(
                radix_evict_demoted=self.radix.num_evict_demoted,
                radix_evict_dropped=self.radix.num_evict_dropped)
        if self.host_store is not None:
            out.update(
                host_pages_used=self.host_store.num_used,
                host_occupancy=self.host_store.occupancy(),
                kv_pages_demoted=self.radix.num_demoted_pages,
                kv_pages_promoted=self.radix.num_promoted_pages,
                host_prefix_hits=self.radix.num_host_hits,
                host_pages_dropped=self.radix.num_host_dropped_pages)
        return out

    def _count_gather(self, sl, P):
        """`_GATHER_COUNTERS` of one plain decode launch: its rows'
        lengths `sl` (padded rows 0) over its P-page table, in every
        layer that decodes through `paged_attention_decode`. Host
        arithmetic over the launch's inputs: no sync."""
        if self._gather_shape is None:
            return
        from ..kernels.paged_attention import paged_decode_page_counts
        kvh, head_dim, cache_dtype, groups = self._gather_shape
        c = self.metrics.counters
        for window, layers in groups:
            tiles, copied = paged_decode_page_counts(
                sl, self.page_size, P, kvh, head_dim, cache_dtype, window)
            c["decode_kv_tile_pages"] += layers * tiles
            c["decode_kv_pages_copied"] += layers * copied

    def _window_gauges(self) -> dict:
        """update_gauges kwargs of the windowed layer groups, empty for
        a model of one group: each group's used pages, and this step's
        part of `_WINDOW_COUNTERS` over the decoding rows. Host
        arithmetic over at most a batch of rows: no sync."""
        groups = self.allocator.windows
        if not groups:
            return {}
        c = self.metrics.counters
        # decoding rows only: a prefilling row's unbounded pages are taken
        # whole at admission and its windowed pages a chunk at a time, so
        # beside it the share would read better than the window earns
        for r in self.scheduler.running:
            c["kv_window_pages_full"] += len(groups) * len(r.seq.pages)
            c["kv_window_pages_held"] += sum(
                r.seq.window_held(g) for g in range(len(groups)))
        c["kv_window_pages_released"] = sum(g.pages_released
                                            for g in groups)
        return {"kv_window_used_pages": [g.pool.num_used for g in groups]}

    # ------------------------------------------------------------- step
    def _emit(self, req: Request, tok: int, emitted):
        """Record one generated token + run the finish checks."""
        first = req.num_generated == 0
        req.output_ids.append(tok)
        if first:
            self.metrics.on_first_token(req.request_id)
            self._tr_mark(req.request_id, "first_token")
        emitted.append((req.request_id, tok))
        if req.eos_token_id is not None and tok == req.eos_token_id:
            return "stop"
        if req.remaining_new_tokens() <= 0:
            return "length"
        return None

    # ------------------------------------------- boundary cancellations
    def _cancel_boundary(self):
        """Iteration-boundary cancellation sweep: apply any injected
        clock skew (deadline-storm fault), then cancel aborted and
        past-deadline requests in ANY state. Valid KV is donated."""
        skew = faults.fire(FAULT_STORM)
        if skew is not None:
            self._clock_skew += float(skew)
        now = self._now()
        for req in list(self.requests.values()):
            if req.state is RequestState.FINISHED:
                continue
            if req.aborted:
                if self.scheduler.cancel(req, "abort"):
                    self.metrics.on_abort(req.request_id)
                    self._tr_finish(req.request_id, "abort")
                    self._retain(req)
            elif req.deadline is not None and now >= req.deadline:
                if self.scheduler.cancel(req, "expired"):
                    self.metrics.on_expire(req.request_id)
                    self._tr_finish(req.request_id, "expired")
                    self._retain(req)

    def _quarantine(self, req: Request):
        """Fail ONE poisoned request, not the engine: no token is
        emitted, its pages are freed WITHOUT donation (they may hold
        NaN K/V — the radix tree must never serve them)."""
        if self.scheduler.cancel(req, "quarantined", donate=False):
            self.metrics.on_quarantine(req.request_id)
            self._tr_mark(req.request_id, "quarantined")
            self._tr_finish(req.request_id, "quarantined")
            self._retain(req)

    def _fail(self, exc: BaseException):
        """Unrecoverable: drain to a serializable snapshot and raise
        EngineFailure. The engine refuses further work afterwards."""
        self.metrics.on_engine_failure()
        self._settle()
        # stamp the FAILING (partial) step into the flight recorder
        # before the snapshot captures the ring — the postmortem's
        # last record is the step that died, not merely the one before
        self.recorder.record({
            "step": int(self.metrics.counters["engine_steps"]) + 1,
            "failed": repr(exc),
            "programs": list(self._step_ev.get("programs", ())),
            "t_wall_ms": (round((time.perf_counter()
                                 - self._step_t0) * 1e3, 3)
                          if self._step_t0 is not None else None),
            "queue_depth": int(self.scheduler.queue_depth),
            "running": len(self.scheduler.running),
            "kv_used_pages": int(self.allocator.num_used),
            "kv_occupancy": round(float(self.allocator.occupancy()), 4),
        })
        self.last_snapshot = self.snapshot(reason=repr(exc))
        self.failed = True
        raise EngineFailure(
            f"unrecoverable engine error: {exc!r}; state drained to "
            f"snapshot ({len(self.last_snapshot['requests'])} requests)",
            snapshot=self.last_snapshot, cause=exc) from exc

    # ------------------------------------------------------------- step
    def step(self):
        """One engine iteration: cancellation sweep, schedule, run
        prefill chunks, run the batched decode step. Returns
        [(request_id, token)] in emission order (empty when idle),
        every token exactly once.

        The plain decode family runs ONE LAUNCH AHEAD (ISSUE 34;
        SERVING.md "The decode loop runs one launch ahead"): before this
        step fetches its decode launch's tokens it enqueues the next
        step's launch, which reads them on the device. The contract:
        (1) every request's tokens are those of the serial order;
        (2) a step returns its own launch's tokens, so between two calls
        at most one decode launch is in flight and it holds only tokens
        not returned yet; (3) a row known to end (by length, an abort,
        a deadline) is left out of the launch ahead, and a row that ends
        only by the token read (`eos_token_id`, not finite) rode in it
        for nothing: that token is never emitted, counted or donated;
        (4) the launch ahead is enqueued only over a quiet step (every
        slot there without a preemption or a page copy; never with a
        proposer or `decode_steps > 1`), else the step is the serial
        order's; (5) `vacate`, `shutdown`, the prefix calls and
        the drain of a failure take the launch in flight back first, and
        `snapshot` holds exactly what has been returned.

        Failure semantics per launch: transients retried by the
        supervisor; a poison failure quarantines the offending
        request(s) and the step continues; anything else drains to a
        snapshot and raises EngineFailure. A failure of the launch
        enqueued ahead is handled after this step's tokens are
        emitted (`_plain_decode_step`)."""
        if self.failed:
            raise EngineFailure("engine has failed; resume from "
                                "last_snapshot", snapshot=self.last_snapshot)
        # one root span a step and one span a phase (PERF.md lists them):
        # `step` is the number this step's flight-recorder record and the
        # RequestTracer's launch spans carry
        self._step_no = int(self.metrics.counters["engine_steps"]) + 1
        with profiler.RecordEvent("serving.step", step=self._step_no):
            return self._step()

    def _step(self):
        emitted = []
        # flight recorder (ISSUE 10): per-step accumulator + counter
        # baseline for the deltas the step record reports
        self._step_t0 = time.perf_counter()
        self._step_ev = {"programs": []}
        _c = self.metrics.counters
        pre = {k: _c[k] for k in (
            "prefill_tokens", "requests_preempted", "step_retries",
            "requests_quarantined", "requests_aborted",
            "deadline_expired", "prefix_hits", "spec_drafted_tokens",
            "spec_accepted_tokens")}
        with profiler.RecordEvent("serving.schedule"):
            self._cancel_boundary()
            sched = self.scheduler.schedule()
            for req in sched.preempted:
                self.metrics.on_preempt()
                self._tr_preempt(req)

        for chunk in sched.prefills:
            req = chunk.request
            if req.state is RequestState.FINISHED:
                continue               # quarantined earlier this step
            if chunk.is_first:
                self.metrics.on_admission(req.request_id,
                                          req.cached_tokens,
                                          resumed=req.num_preemptions > 0)
                self._tr_admit(req, resumed=req.num_preemptions > 0)
            try:
                tok, ok = self._run_chunk(chunk)
            except Exception as exc:   # noqa: BLE001
                if classify_failure(exc) == POISON:
                    self._quarantine(req)
                    continue
                self._fail(exc)
            with profiler.RecordEvent("serving.emit"):
                self._after_chunk(chunk, tok, ok, emitted)

        decodes = [r for r in sched.decodes
                   if r.state is not RequestState.FINISHED]
        if decodes or self._flight is not None:
            for req in decodes:
                self._apply_copies(req.pending_copies)
                req.pending_copies = []
            if self.proposer is not None:
                self._spec_decode_step(decodes, emitted)
            elif self.decode_steps > 1:
                self._multi_decode_step(decodes, emitted)
            else:
                self._plain_decode_step(decodes, emitted)

        with profiler.RecordEvent("serving.bookkeeping"):
            self.metrics.on_step()
            self.metrics.update_gauges(
                queue_depth=self.scheduler.queue_depth,
                running=len(self.scheduler.running),
                kv_used_pages=self.allocator.num_used,
                kv_occupancy=self.allocator.occupancy(),
                cached_pages=(self.radix.num_cached_pages
                              if self.radix else 0),
                radix_nodes=self.radix.num_nodes if self.radix else 0,
                radix_evicted_pages=(self.radix.num_evicted_pages
                                     if self.radix else None),
                **self._spill_gauges(), **self._window_gauges())
            self._record_step(pre, n_chunks=len(sched.prefills),
                              n_decode=len(decodes),
                              n_emitted=len(emitted))
        return emitted

    def _after_chunk(self, chunk, tok, ok, emitted):
        """What a chunk's result does to its request: quarantine, or the
        computed length and, after the last chunk, the first token and
        finish / hand-off / joining the decode batch."""
        req = chunk.request
        if not ok:
            self._quarantine(req)
            return
        req.num_computed = chunk.start + chunk.length
        if not chunk.is_last:
            return
        reason = self._emit(req, tok, emitted)
        if reason is not None:
            self.scheduler.finish(req, reason)
            self._on_finished(req)
        elif self.role == "prefill" and not req.colocate:
            # disaggregated prefill (ISSUE 18): the request's
            # block-aligned pages donate to the radix tree and the
            # request finishes "handoff" instead of joining the decode
            # batch — the fleet pulls the pages to a decode-role worker
            # via export_prefix. The first token was already emitted
            # above, so the decode side resumes from index 1 with zero
            # token loss.
            req.handoff_prefix_len = self.scheduler.finish_handoff(req)
            self.metrics.counters["prefill_handoffs"] += 1
            self._on_finished(req)
        else:
            self.scheduler.on_prefilled(req)

    def _record_step(self, pre: Dict[str, int], *, n_chunks: int,
                     n_decode: int, n_emitted: int):
        """Append this iteration's StepRecord to the flight recorder.
        Idle steps (nothing scheduled, nothing cancelled) are skipped so
        a quiet polling loop cannot evict the history that matters."""
        c = self.metrics.counters
        rec = {
            "step": int(c["engine_steps"]),
            "t_wall_ms": round((time.perf_counter()
                                - self._step_t0) * 1e3, 3),
            "programs": list(self._step_ev["programs"]),
            "prefill_chunks": int(n_chunks),
            "prefill_tokens": int(c["prefill_tokens"]
                                  - pre["prefill_tokens"]),
            "decode_batch": int(n_decode),
            # tokens-per-launch context under coarser launches
            # (ISSUE 13): K=1 for the plain decode program, the launch
            # K bucket for multi-step decode, K+1 for a speculative
            # verify launch, 0 for no decode-side launch this step
            "decode_k": int(self._step_ev.get("decode_k", 0))
            if n_decode else 0,
            # whether the step's plain decode launch had been enqueued
            # by the step before, ahead of that step's fetch (ISSUE 34)
            "decode_ahead": bool(self._step_ev.get("decode_ahead", False)),
            "tokens_out": int(n_emitted),
            "preempted": int(c["requests_preempted"]
                             - pre["requests_preempted"]),
            "retries": int(c["step_retries"] - pre["step_retries"]),
            "quarantined": int(c["requests_quarantined"]
                               - pre["requests_quarantined"]),
            "aborted": int(c["requests_aborted"]
                           - pre["requests_aborted"]),
            "expired": int(c["deadline_expired"]
                           - pre["deadline_expired"]),
            "prefix_hits": int(c["prefix_hits"] - pre["prefix_hits"]),
            "spec_drafted": int(c["spec_drafted_tokens"]
                                - pre["spec_drafted_tokens"]),
            "spec_accepted": int(c["spec_accepted_tokens"]
                                 - pre["spec_accepted_tokens"]),
            "queue_depth": int(self.scheduler.queue_depth),
            "running": len(self.scheduler.running),
            "kv_used_pages": int(self.allocator.num_used),
            "kv_occupancy": round(float(self.allocator.occupancy()), 4),
            "cached_pages": int(self.radix.num_cached_pages
                                if self.radix else 0),
        }
        if rec["programs"] or any(
                rec[k] for k in ("prefill_chunks", "decode_batch",
                                 "tokens_out", "preempted", "aborted",
                                 "expired", "quarantined")):
            self.recorder.record(rec)

    def timeline(self) -> List[dict]:
        """Flight-recorder view: the last N non-idle StepRecords,
        oldest first (ISSUE 10). The same list rides every snapshot."""
        return self.recorder.records()

    def _plain_decode_step(self, decodes: List[Request], emitted):
        """The plain decode family's part of a step: return the tokens
        of THIS step's launch over `decodes`, with the next step's
        launch enqueued before they are fetched where the step is quiet.

        This step's launch is the one the last step enqueued ahead, or
        is enqueued now (the serial order: the first decode step, and
        any step after one that was not quiet). Then, BEFORE its tokens
        are fetched, `_launch_ahead` reserves the next slot of every row
        that goes on and enqueues the next launch, its input ids taken
        on the device from this launch's tokens; the host fetches, keeps
        its books and emits for this launch while the device runs that
        one. One launch ahead, never more.

        A row in `decodes` is a row of the launch (the scheduler adds to
        the decode batch only what `_launch_ahead` saw); a row of the
        launch that is no longer in `decodes` finished while it was in
        flight, and its token is dropped unread.

        Failures: of this step's launch (enqueue or fetch) as ever, a
        poison failure isolates the rows in solo launches and anything
        else drains to a snapshot. Of the launch ahead: its slots are
        given back, this step's tokens are emitted first, and then
        anything but poison drains to a snapshot, with those tokens in
        it; after a poison failure the next step enqueues its launch in
        the serial order and isolates there if it fails again."""
        flight, self._flight = self._flight, None
        if not decodes:
            # the boundary cancelled every row of the launch in flight:
            # it is dropped unread (each row gave its pages back whole)
            return
        ahead = ahead_exc = None
        try:
            if flight is None:
                flight = self._enqueue_decode(decodes)
            try:
                ahead = self._launch_ahead(flight)
            except Exception as exc:   # noqa: BLE001
                ahead_exc = exc
            rows = self._fetch_decode(flight, decodes)
        except Exception as exc:   # noqa: BLE001
            # what was enqueued ahead is taken back before anything else
            self._flight, ahead, flight = ahead, None, None
            self._settle()
            if classify_failure(exc) != POISON:
                self._fail(exc)
            # unattributed poison (a FloatingPointError raised by an
            # eager/dispatch NaN hook instead of the in-graph flags):
            # isolate by running rows solo
            rows = list(zip(decodes, *self._isolate_poisoned(decodes)))
        with profiler.RecordEvent("serving.emit"):
            n0 = len(emitted)
            for req, tok, ok in rows:
                if not ok:
                    self._quarantine(req)
                    continue
                reason = self._emit(req, int(tok), emitted)
                if reason is not None:
                    self.scheduler.finish(req, reason)
                    self._on_finished(req)
            if flight is not None:
                # TPOT sample: launch wall seconds / tokens emitted, so
                # the per-token percentiles stay comparable across K
                # (ISSUE 13)
                self.metrics.on_decode_launch(1, len(decodes),
                                              len(emitted) - n0,
                                              self._last_launch_s,
                                              ahead=flight.ahead)
            else:
                # solo isolation launches counted decode_tokens in
                # _fetch_decode; keep the tokens-per-launch denominator
                # honest (no TPOT sample — solo timings aren't a batch
                # launch's)
                self.metrics.on_decode_launch(1, len(decodes), 0, None)
        if ahead_exc is not None and \
                classify_failure(ahead_exc) != POISON:
            self._fail(ahead_exc)
        # a launch nobody rides any more (every row's end was read from
        # this step's tokens) is dropped here, unread
        if ahead is not None and any(r.reserved_ahead for r in ahead.reqs):
            self._flight = ahead

    def _launch_ahead(self, flight: _DecodeLaunch):
        """Enqueue the launch after `flight` before `flight`'s tokens
        are fetched, if the next step is quiet by what can be seen now;
        returns it, or None where the serial order stands.

        Its rows are what the next `schedule()` would decode: the
        scheduler's decode batch less every row known to end first (a
        row of `flight` whose pending token is its last by length; a row
        already aborted or past its deadline, which the next boundary
        cancels). What only the pending token can tell (an
        `eos_token_id` hit, a row not finite) is not waited for: such a
        row rides along and its token of that launch is dropped. Quiet
        means: each row's slot is there without preempting anybody or
        copying a page, at most for a cached prefix nobody uses
        (`Scheduler.reserve_ahead`), so nothing the next `schedule()`
        would have decided about live work is decided here. Raises what
        the enqueue raises, with the slots given back."""
        now = self._now()
        rows = [r for r in self.scheduler.running
                if not r.aborted
                and not (r.deadline is not None and now >= r.deadline)
                and not (r in flight.row and r.remaining_new_tokens() <= 1)]
        if not rows or not self.scheduler.reserve_ahead(rows):
            return None
        try:
            return self._enqueue_decode(rows, flight)
        except Exception:
            self.scheduler.release_ahead(rows)
            raise

    def _settle(self):
        """Leave nothing in flight: called by whatever touches the pool,
        the prefix tree or the requests from outside `step()`, so that
        it sees the state the serial order has after the last returned
        token. The launch enqueued ahead is TAKEN BACK: its rows' slots
        are given back and its tokens are never read, so each row's next
        launch reads the row's last RETURNED token again and computes
        the same one. The device still runs the launch; what it writes
        lies beyond every row's computed length (never donated) and the
        next launch of the row, or of whoever holds the page by then,
        writes over it, since the device runs launches in the order they
        were enqueued."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self.scheduler.release_ahead(flight.reqs)

    def _isolate_poisoned(self, reqs: List[Request]):
        """Degraded mode for an UNATTRIBUTED poison failure of a decode
        batch: re-run each row as a solo launch to find the poisoned
        request(s), returning (toks, oks) for the caller to emit or
        quarantine from. Solo launches are idempotent K/V-wise (same
        tokens written at the same positions) but use the B=1 bucket —
        a different program shape, so this path trades the cross-shape
        bit-identity guarantee for failure isolation (greedy tokens in
        practice agree; SERVING.md documents the caveat)."""
        toks = np.zeros((len(reqs),), np.int64)
        oks = np.ones((len(reqs),), bool)
        for i, req in enumerate(reqs):
            try:
                [(_, t, o)] = self._fetch_decode(
                    self._enqueue_decode([req]), [req])
            except Exception as exc:   # noqa: BLE001
                if classify_failure(exc) == POISON:
                    oks[i] = False
                    continue
                self._fail(exc)
            toks[i] = int(t)
            oks[i] = bool(o)
        return toks, oks

    def _retain(self, req: Request):
        """Terminal-request retention bookkeeping (bounded window).
        Every terminal path funnels here, so it doubles as the
        proposer's release hook (a KV-owning proposer frees its draft
        pages for this request) and the adapter-refcount release
        (ISSUE 15: a terminal request unpins its adapter, making it
        eviction-eligible again once idle)."""
        if self.proposer is not None:
            self.proposer.on_finished(req)
        if self.lora is not None and req.adapter is not None:
            self.lora.release(req.adapter)
        self._finished_order.append(req.request_id)
        while len(self._finished_order) > self.max_retained_finished:
            self.requests.pop(self._finished_order.pop(0), None)
            self.num_evicted_finished += 1

    def _on_finished(self, req: Request):
        self.metrics.on_finish(req.request_id)
        self._tr_finish(req.request_id, req.finish_reason or "stop")
        self._retain(req)

    # --------------------------------------------------- snapshot/resume
    def snapshot(self, reason: str = "requested", *,
                 include_recorder: bool = True) -> dict:
        """Serializable drain state: every non-finished request (queued,
        mid-prefill, decoding, preempted) with its prompt, tokens
        generated so far, and remaining deadline. Device state (KV
        pages) is deliberately NOT captured — it is lost with the device
        anyway; a resumed request re-prefills prompt+generated exactly
        like a preemption resume, so greedy outputs stay bit-identical
        under the same bucket grid. JSON-roundtrip-safe by construction
        (plain ints/floats/lists only). `include_recorder=False` drops
        the flight-recorder ring — the cross-process worker's
        heartbeats ship a snapshot ~20x/s and the supervisor only reads
        the request records, so the postmortem payload stays on the
        drain/failure snapshots where it is read. A decode launch in
        flight (ISSUE 34) is neither in the snapshot nor disturbed by
        it: a record holds the tokens `step()` has returned, the launch
        has returned none, and whoever resumes computes its token
        again."""
        now = self._now()
        recs = []
        for req in self.requests.values():
            if req.state is RequestState.FINISHED:
                continue
            recs.append({
                "request_id": int(req.request_id),
                "prompt_ids": [int(t) for t in req.prompt_ids],
                "output_ids": [int(t) for t in req.output_ids],
                "max_new_tokens": int(req.max_new_tokens),
                "eos_token_id": (None if req.eos_token_id is None
                                 else int(req.eos_token_id)),
                "num_preemptions": int(req.num_preemptions),
                "aborted": bool(req.aborted),
                "deadline_remaining_s": (
                    None if req.deadline is None
                    else float(req.deadline - now)),
                # ISSUE 15 (snapshot minor 2): the adapter rides the
                # record so failover re-lands the request WITH its
                # adapter (or refuses typed) — never wrong-adapter
                "adapter": req.adapter,
                # ISSUE 18 (snapshot minor 3): a supervisor-pinned
                # colocate flag survives migration — a role-starved
                # fallback must stay decodable wherever it re-lands
                "colocate": bool(req.colocate),
            })
        recs.sort(key=lambda r: r["request_id"])   # FCFS order on resume
        snap = {"version": SNAPSHOT_VERSION, "minor": SNAPSHOT_MINOR,
                "reason": str(reason),
                "rng_key": np.asarray(self._key).tolist(),
                "requests": recs}
        if include_recorder:
            # the engine's last N non-idle StepRecords ride every
            # snapshot (ISSUE 10): an engine_failures postmortem
            # reads the context straight out of the drain state.
            # from_snapshot/adopt ignore the key, so the schema
            # version is unchanged — old snapshots resume fine.
            snap["flight_recorder"] = self.recorder.records()
        return snap

    def _restore_request(self, rec: dict) -> Request:
        """Rebuild one snapshot request record into THIS engine under
        its ORIGINAL id: generated tokens fold into the resume prompt
        (the preemption recompute path), the remaining deadline is
        re-anchored on this engine's clock, and the admission bound is
        bypassed (restored work was already admitted once — shedding it
        would drop accepted work). An adapter'd record REQUIRES its
        adapter loaded here (typed AdapterNotLoaded otherwise): a
        migrated request must re-land with the adapter or not at all —
        the fleet parks it typed, never serves the wrong weights."""
        adapter = rec.get("adapter")
        if adapter is not None and (self.lora is None
                                    or not self.lora.has(adapter)):
            self.metrics.counters["adapter_rejects"] += 1
            raise AdapterNotLoaded(
                f"snapshot request {rec['request_id']} needs adapter "
                f"{adapter!r}, which this engine does not hold",
                adapter=adapter)
        req = Request(rec["prompt_ids"], rec["max_new_tokens"],
                      rec.get("eos_token_id"),
                      request_id=rec["request_id"], adapter=adapter)
        if len(req.prompt_ids) + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"snapshot request {req.request_id} needs "
                f"{len(req.prompt_ids) + req.max_new_tokens} tokens "
                f"> resumed engine max_seq_len {self.max_seq_len}")
        req.output_ids = [int(t) for t in rec.get("output_ids", [])]
        req.num_preemptions = int(rec.get("num_preemptions", 0))
        req.aborted = bool(rec.get("aborted", False))
        req.colocate = bool(rec.get("colocate", False))
        rem = rec.get("deadline_remaining_s")
        if rem is not None:
            req.deadline = self._now() + float(rem)
        self.scheduler.add_request(req, force=True)
        if adapter is not None:
            self.lora.acquire(adapter)     # pinned until terminal
            # THIS engine's load generation namespaces the radix key —
            # the adopting registry's weights are what will serve it
            req.adapter_key = self.lora.namespace_of(adapter)
        self.requests[req.request_id] = req
        # adopted, not added: a migrated request already counted as an
        # arrival on its original engine, and fleet summaries merge
        # counters across ALL replicas (dead ones included)
        self.metrics.on_adopt(req.request_id)
        if self.tracer is not None:
            # with a fleet-shared tracer the migrated request's LIVE
            # trace continues here (begin is idempotent); a fresh
            # from_snapshot engine starts a new one at the adopt mark
            tr = self.tracer.begin(req.request_id,
                                   engine=self.metrics.name,
                                   prompt_len=len(req.prompt_ids),
                                   max_new_tokens=req.max_new_tokens)
            now = self.tracer.now_ns()
            tr.mark("adopt", now, engine=self.metrics.name,
                    tokens_so_far=len(req.output_ids))
            tr.t_queue = now      # re-queued on the adopting engine
        return req

    def adopt_requests(self, recs) -> List[int]:
        """Live-migration intake: restore snapshot request records into
        this RUNNING engine (the fleet re-lands a dead or draining
        replica's work on survivors this way — `from_snapshot` minus
        the fresh-engine construction). Requests keep their original
        ids (unique process-wide: ids come from one global counter, and
        the counter is bumped past restored ids for the cross-process
        case). Greedy continuations are bit-identical to an
        uninterrupted run under the same bucket grid; this engine's OWN
        rng key stream serves any sampled continuation. Returns the
        adopted request ids."""
        if self.failed:
            raise EngineFailure("engine has failed; resume from "
                                "last_snapshot",
                                snapshot=self.last_snapshot)
        ids = []
        for rec in recs:
            ids.append(self._restore_request(rec).request_id)
        if ids:
            bump_request_counter(max(ids))
        return ids

    def vacate(self, reason: str = "migrated") -> int:
        """Release every KV page this engine holds: cancel all
        non-finished requests locally (no donation — the work is not
        lost, it re-lands elsewhere via `adopt_requests`; no
        abort/expired metrics for the same reason) and drop the radix
        tree. Pure host bookkeeping, so it works on a FAILED engine —
        the fleet calls this on a dead replica's pool and then asserts
        full page/refcount reclamation. A decode launch in flight is
        taken back first: its token was never returned, and whoever
        adopts the request computes it again. Returns pages freed."""
        self._settle()
        before = self.allocator.num_free
        for req in list(self.requests.values()):
            if req.state is not RequestState.FINISHED:
                if self.scheduler.cancel(req, reason, donate=False):
                    self._retain(req)
        self.reset_prefix_cache()
        # refresh the metric gauges NOW: a vacated (usually dead) engine
        # never steps again, so without this its last mid-flight gauges
        # would sit in every future fleet-merged summary as phantom
        # queue depth / used pages
        self.metrics.update_gauges(
            queue_depth=self.scheduler.queue_depth,
            running=len(self.scheduler.running),
            kv_used_pages=self.allocator.num_used,
            kv_occupancy=self.allocator.occupancy(),
            cached_pages=self.radix.num_cached_pages if self.radix else 0,
            radix_nodes=self.radix.num_nodes if self.radix else 0,
            radix_evicted_pages=(self.radix.num_evicted_pages
                                 if self.radix else None),
            **self._spill_gauges())
        return self.allocator.num_free - before

    @classmethod
    def from_snapshot(cls, model, snapshot: dict, **engine_kw):
        """Build a fresh engine that resumes a drained one. Restored
        requests keep their ORIGINAL ids (the global id counter is
        bumped past them) and re-enter WAITING with their generated
        tokens folded into the resume prompt — the same recompute path
        a preemption uses. Greedy outputs complete bit-identically
        given the same bucket grid; the sampled-path key stream is
        restored but its position reflects the resume's chunking, so
        sampled continuations are reproducible per snapshot, not
        bit-equal to the uninterrupted run. Raises the typed
        `SnapshotVersionError` on a schema-version mismatch — resuming
        a snapshot this build would misread must fail loud."""
        check_snapshot_version(snapshot)
        eng = cls(model, **engine_kw)
        eng._key = jnp.asarray(np.asarray(snapshot["rng_key"], np.uint32))
        eng.adopt_requests(snapshot["requests"])
        return eng

    # --------------------------------------------------- prefix cache ops
    def reset_prefix_cache(self) -> int:
        """Drop every cached prefix (the tree's page refs release);
        returns the number of pages returned to the free list. With no
        live requests this brings allocator occupancy back to zero —
        the drain-reclamation check in the acceptance test. Takes a
        decode launch in flight back first (`_settle`)."""
        self._settle()
        if self.radix is None:
            return 0
        return self.radix.clear()

    # -------------------------------- fleet prefix sharing (ISSUE 17)
    def export_prefix(self, tokens) -> tuple:
        """Fleet KV pull, DONOR side: the longest DEVICE-resident
        cached prefix of `tokens` as (num_tokens, [payload bytes, one
        per page]). The payloads are the same CRC-protected codec the
        spill tier demotes with, so they chunk straight into PR-14
        mailbox frames. promote_budget=0 pins the walk to the device
        tier — a pull must never charge this engine's own prefill
        budget or its device pool for a sibling's benefit. The LRU bump
        is deliberate: a pulled prefix is hot. Takes a decode launch in
        flight back first (`_settle`)."""
        self._settle()
        if self.radix is None:
            return 0, []
        pages, m = self.radix.match(tokens, promote_budget=0)
        if not pages:
            return 0, []
        payloads = [self._gather_page_payload(pid) for pid in pages]
        self.metrics.counters["kv_pages_exported"] += len(payloads)
        return m, payloads

    def adopt_prefix(self, tokens, payloads) -> int:
        """Fleet KV pull, RECEIVER side: land a sibling's exported
        prefix pages in this engine's caches and donate them to the
        radix tree (so the next admission matches them like any local
        prefix). Degrades to 0 — never raises — on a corrupt payload,
        a dry device pool, or a span the tree already holds: a failed
        pull just means the prefix recomputes, exactly the spill tier's
        fallback contract. Takes a decode launch in flight back first
        (`_settle`). Returns pages newly adopted."""
        self._settle()
        if self.radix is None or not payloads:
            return 0
        n = min(len(payloads) * self.page_size,
                (len(tokens) // self.page_size) * self.page_size)
        payloads = payloads[:n // self.page_size]
        if not payloads:
            return 0
        try:
            arrays = [decode_page_payload(p) for p in payloads]
        except HostPageCorrupt:
            self.metrics.counters["host_spill_corrupt"] += 1
            return 0
        try:
            pids = self.allocator._alloc_pages(len(arrays))
        except BlocksExhausted:
            return 0
        try:
            for pid, arrs in zip(pids, arrays):
                self._scatter_page_payload(pid, arrs)
        except HostPageCorrupt:
            self.metrics.counters["host_spill_corrupt"] += 1
            for pid in pids:
                self.allocator._decref(pid)
            return 0
        adopted = self.radix.insert(tuple(tokens[:n]), pids)
        # the tree took its own refs on the pages it adopted; drop the
        # intake refs — duplicate pages (spans already cached) free here
        for pid in pids:
            self.allocator._decref(pid)
        self.metrics.counters["kv_pages_adopted"] += adopted
        return adopted

    def release_prefix(self, tokens, *, drop: bool = False) -> int:
        """Release-after-handoff page accounting (ISSUE 18): once this
        engine's pages for `tokens` were shipped to AND adopted by a
        decode-role sibling, the local copy stops earning its pool
        space on its own merits. Default: DEMOTE the cached span to
        coldest LRU rank — it stays matchable (a shared prompt prefix
        keeps serving future admissions, and a later match re-heats
        it), but it is the FIRST eviction victim under pressure, so a
        prefill-role pool can never fill with spans that already live
        on decode workers. `drop=True` frees the deepest childless
        nodes of the span outright (strict accounting — tests assert
        exact reclamation with it). Takes a decode launch in flight
        back first (`_settle`). Returns pages demoted/freed."""
        self._settle()
        if self.radix is None:
            return 0
        chain = [child for child, _ in self.radix._walk_prefix(tokens)]
        released = 0
        if drop:
            before = self.allocator.num_free
            for node in reversed(chain):
                # only childless device-resident tails: dropping an
                # interior node would orphan descendants reachable by
                # other requests' prefixes
                if node.children or node.host_pages:
                    break
                self.radix._drop_node(node)
            released = self.allocator.num_free - before
        else:
            for node in chain:
                node.last_use = 0       # coldest: first eviction victim
                released += len(node.pages)
        self.metrics.counters["kv_pages_released"] += released
        return released

    # ------------------------------------------------------- convenience
    def stream(self):
        """Generator over (request_id, token) until all work drains."""
        while self.has_work():
            for item in self.step():
                yield item

    def run(self) -> Dict[int, List[int]]:
        """Drain everything; returns {request_id: generated tokens} for
        every request alive when run() was called — tokens are collected
        from step() emissions, so results survive even when the bounded
        finished-retention window evicts the Request object mid-drain."""
        out = {rid: list(r.output_ids) for rid, r in self.requests.items()}
        guard = 0
        limit = 16 * (self.max_seq_len + 2) * max(1, len(self.requests))
        while self.has_work():
            for rid, tok in self.step():
                out.setdefault(rid, []).append(tok)
            guard += 1
            if guard > limit:
                raise RuntimeError("serving engine failed to drain "
                                   f"after {guard} steps")
        return out

    def shutdown(self):
        self._settle()
        if self.proposer is not None:
            self.proposer.reset()
        self.metrics.unregister()
