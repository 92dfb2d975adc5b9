"""Iteration-level continuous-batching scheduler.

Follows Orca's iteration-level scheduling (Yu et al., OSDI '22): every
engine step re-forms the batch from whatever is in flight, so a finishing
request's slot is reused immediately instead of waiting for the whole
batch to drain. Admission is FCFS under a per-step token budget; memory
pressure is resolved by cached-prefix LRU eviction first (radix tree,
when enabled), then preempt-by-eviction (vLLM-style recompute
preemption: the victim's pages are freed and it re-enters the waiting
queue with its generated tokens folded into the prompt).

Two serving optimizations ride the same admission path (ISSUE 2):

* **Radix prefix reuse** (SGLang RadixAttention): intake matches the
  longest block-aligned cached prefix of the (resume) prompt, shares
  those pages through the allocator's refcounts, and skips their
  prefill; finished/preempted sequences donate their full pages back.
* **Chunked prefill** (Sarathi-Serve): a prompt is processed in
  token-budget-sized CHUNKS interleaved with ongoing decode steps —
  a long prompt no longer monopolizes an engine step, and the old
  "oversized prompts admitted alone" special case is gone: any positive
  budget admits the head-of-line request with a budget-sized first
  chunk.

Per-request state machine:

    WAITING --admit--> PREFILL --last chunk + first token--> DECODE
       ^               (1..k chunk steps)                      |
       +---------------------- preempt ------------------------+
                                                  --eos/len--> FINISHED

`cancel()` exits any live state (queued, chunk-prefilling, decoding,
preempted-and-waiting) into FINISHED at an iteration boundary, with the
finish_reason recording why ("abort" / "expired" / "quarantined").
Aborted and expired requests DONATE their computed pages to the radix
cache (their KV is valid — the client just stopped wanting it);
quarantined requests never donate (their pages may hold NaN K/V).

The scheduler is pure host logic and deterministic: given the same
arrival sequence and the same allocator geometry it produces the same
step-by-step batch composition (golden-trace tested; the radix LRU uses
a monotonic counter, never wall-clock).
"""
from __future__ import annotations

import enum
import itertools
from collections import deque
from typing import List, Optional

from .kv_cache import BlockAllocator, BlocksExhausted

__all__ = ["RequestState", "Request", "PrefillChunk", "ScheduleStep",
           "Scheduler", "adapter_prefix_key"]


def adapter_prefix_key(ids, adapter):
    """Radix-cache key for a (possibly adapter'd) token sequence
    (ISSUE 15): a request served under a LoRA adapter namespaces every
    token with the adapter id, so identical token prefixes under
    different adapters (or adapter vs base) can NEVER share cached KV
    pages — their K/V differ by the adapter delta. Length-preserving,
    so all page-alignment math is untouched; the tree compares tokens
    by equality only, so tuple tokens slot straight in."""
    if adapter is None:
        return ids
    return [(adapter, t) for t in ids]


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"


_req_counter = itertools.count()


def bump_request_counter(beyond: int):
    """Advance the global request-id counter past `beyond` — resuming a
    snapshot restores requests under their ORIGINAL ids, and new
    requests added afterwards must not collide with them."""
    global _req_counter
    nxt = next(_req_counter)
    if nxt <= beyond:
        _req_counter = itertools.count(beyond + 1)
    else:
        _req_counter = itertools.count(nxt)


class Request:
    """One generation request tracked through the state machine."""

    def __init__(self, prompt_ids, max_new_tokens: int,
                 eos_token_id: Optional[int] = None,
                 request_id: Optional[int] = None,
                 adapter: Optional[str] = None):
        self.request_id = (next(_req_counter) if request_id is None
                           else request_id)
        self.prompt_ids = [int(t) for t in prompt_ids]
        if not self.prompt_ids:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.eos_token_id = eos_token_id
        # LoRA adapter name (ISSUE 15; None = base model). Rides the
        # launch slot mapping and snapshots. `adapter_key` is the
        # radix-namespace token: the ENGINE overrides it with the
        # registry's (name, load-generation) so prefixes cached under
        # replaced weights of the same name can never match — the bare
        # name is only the registry-less default.
        self.adapter = adapter
        self.adapter_key = adapter
        self.state = RequestState.WAITING
        self.output_ids: List[int] = []
        self.seq = None                 # KVSequence while holding pages
        self.pending_copies = []        # CoW copies due before this step
        # the slot of the NEXT step's decode launch is already reserved
        # (`Scheduler.reserve_ahead`: the engine enqueued that launch
        # before this step's tokens were fetched)
        self.reserved_ahead = False
        self.num_preemptions = 0
        self.finish_reason: Optional[str] = None
        self.arrival = self.request_id  # FCFS key (monotonic ids)
        # tokens whose K/V is valid in the paged cache (cached-prefix
        # match at admission + every chunk/decode write; maintained by
        # the scheduler at admission and the engine after each launch)
        self.num_computed = 0
        # cached-prefix tokens matched at the LAST admission
        self.cached_tokens = 0
        # --- resilience (ISSUE 3) ---
        # absolute engine-clock deadline (None = no TTL); the engine
        # cancels past-deadline requests at each iteration boundary
        self.deadline: Optional[float] = None
        # set by ServingEngine.abort(); honored at the next boundary
        self.aborted = False
        # --- disaggregated prefill/decode (ISSUE 18) ---
        # colocate=True pins the request to local decode even on a
        # prefill-role engine (the fleet's role-starved fallback);
        # handoff_prefix_len records the block-aligned token span
        # donated by finish_handoff — the span the fleet's kv_pull
        # ships to the decode-role adopter
        self.colocate = False
        self.handoff_prefix_len = 0

    # prompt the next prefill must process (original prompt + anything
    # generated before a preemption — recompute-style resume)
    @property
    def resume_ids(self) -> List[int]:
        return self.prompt_ids + self.output_ids

    @property
    def num_generated(self) -> int:
        return len(self.output_ids)

    def remaining_new_tokens(self) -> int:
        return self.max_new_tokens - self.num_generated

    def __repr__(self):
        return (f"Request({self.request_id}, {self.state.name}, "
                f"prompt={len(self.prompt_ids)}, out={len(self.output_ids)})")


class PrefillChunk:
    """One scheduled prefill chunk: process resume_ids[start:start+length]
    (is_last == the chunk reaches the prompt end, so the engine samples
    the first token from its final live position)."""

    __slots__ = ("request", "start", "length", "is_last", "is_first")

    def __init__(self, request, start, length, is_last, is_first):
        self.request = request
        self.start = start
        self.length = length
        self.is_last = is_last
        self.is_first = is_first

    @property
    def request_id(self):
        return self.request.request_id

    def __repr__(self):
        return (f"PrefillChunk(req={self.request_id}, "
                f"[{self.start}:{self.start + self.length}]"
                f"{' last' if self.is_last else ''})")


class ScheduleStep:
    """One engine step's worth of work: prefill chunks (each runs as its
    own bucketed program) + the decode batch."""

    __slots__ = ("prefills", "decodes", "preempted")

    def __init__(self, prefills, decodes, preempted):
        self.prefills = prefills
        self.decodes = decodes
        self.preempted = preempted

    def is_empty(self):
        return not (self.prefills or self.decodes)


class Scheduler:
    """FCFS continuous-batching scheduler over a BlockAllocator.

    token_budget caps the tokens processed per step (each decode request
    costs 1, a prefill chunk costs its length) — the knob that trades
    time-to-first-token against decode throughput when prefills and
    decodes interleave. max_batch_size caps concurrent in-flight
    (PREFILL/DECODE) requests, which bounds the decode batch bucket.
    prefix_cache (a RadixCache over the same allocator, or None) enables
    cached-prefix reuse + donation.
    """

    def __init__(self, allocator: BlockAllocator, max_batch_size: int = 8,
                 token_budget: int = 512,
                 max_prompt_len: Optional[int] = None,
                 prefix_cache=None,
                 max_queue_len: Optional[int] = None):
        self.allocator = allocator
        self.max_batch_size = int(max_batch_size)
        self.token_budget = int(token_budget)
        self.max_prompt_len = max_prompt_len
        self.prefix_cache = prefix_cache
        # admission control: bound on len(waiting). A preempted request
        # re-entering the queue is NOT subject to it (it was already
        # admitted once; shedding it would drop accepted work).
        self.max_queue_len = (None if max_queue_len is None
                              else int(max_queue_len))
        # per-step token cost of one decoding request. Plain decode = 1;
        # the spec-decode engine sets 1 + spec_k so the verify tokens
        # (draft positions scored per sequence per step) are charged
        # against the same budget prefill chunks draw from — otherwise
        # speculative steps would silently blow the TTFT-vs-throughput
        # contract the budget exists to enforce. The multi-step decode
        # engine (ISSUE 13) sets decode_steps for the same reason: one
        # schedule() decision now covers a K-token launch, so admission
        # and preemption at K-step boundaries must see the true
        # per-launch token traffic.
        self.decode_token_cost = 1
        self.waiting: deque = deque()
        self.prefilling: List[Request] = []   # admitted, chunks pending
        self.running: List[Request] = []      # decoding, arrival order
        self.num_preemptions = 0

    # ---- intake ----------------------------------------------------------
    def add_request(self, req: Request, force: bool = False):
        """Queue `req` (FCFS). `force=True` bypasses the admission bound
        — used for snapshot-restored requests, which were admitted once
        already; validation still applies."""
        if self.max_prompt_len is not None and \
                len(req.prompt_ids) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(req.prompt_ids)} exceeds engine "
                f"max_prompt_len {self.max_prompt_len}")
        cap = (self.allocator.num_pages - 1) * self.allocator.page_size
        if len(req.prompt_ids) + req.max_new_tokens > cap:
            raise ValueError(
                f"request needs {len(req.prompt_ids) + req.max_new_tokens} "
                f"tokens of KV > total capacity {cap}")
        if not force and self.max_queue_len is not None and \
                len(self.waiting) >= self.max_queue_len:
            from .errors import EngineOverloaded
            raise EngineOverloaded(
                f"waiting queue full ({len(self.waiting)} >= "
                f"max_queue_len {self.max_queue_len})",
                queue_depth=len(self.waiting),
                max_queue_len=self.max_queue_len)
        req.state = RequestState.WAITING
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.prefilling or self.running)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def num_in_flight(self) -> int:
        return len(self.running) + len(self.prefilling)

    # ---- prefix cache plumbing ------------------------------------------
    def _donate(self, req: Request):
        """Hand the request's computed full pages to the radix tree
        (finish AND preemption both donate — an evicted victim's resume
        then re-matches its own prefix instead of recomputing it)."""
        if self.prefix_cache is None or req.seq is None:
            return
        # adapter-namespaced key (ISSUE 15): an adapter'd request's KV
        # holds the adapter delta — it must never serve another
        # adapter's (or the base model's, or a RELOADED same-name
        # adapter's) identical token prefix
        ids = adapter_prefix_key(req.prompt_ids + req.output_ids,
                                 req.adapter_key)
        n = min(req.num_computed, len(ids), req.seq.num_tokens)
        ps = self.allocator.page_size
        full = (n // ps) * ps
        if full:
            try:
                self.prefix_cache.insert(ids[:full],
                                         req.seq.pages[:full // ps])
            except Exception:
                # a failed donation (e.g. injected fault) only costs a
                # future cache hit; the donor still frees normally and
                # the tree was not mutated (insert raises before any
                # adoption), so reclamation stays exact
                pass

    def _reclaim(self, need_pages: int, protect=()) -> bool:
        """Cached-prefix LRU eviction — ALWAYS tried before preempting a
        live request (SERVING.md eviction ordering)."""
        if self.prefix_cache is None or need_pages <= 0:
            return False
        return self.prefix_cache.evict(need_pages, protect) >= need_pages

    # ---- preemption ------------------------------------------------------
    def _preempt_one(self, keep: Request) -> Optional[Request]:
        """Evict the LAST-arrived in-flight request (decoding OR
        mid-prefill) — possibly `keep` itself when IT is the newest
        (strict FCFS priority: a newer request never survives at an
        older one's expense). The victim donates its computed pages to
        the prefix cache (when enabled), frees the rest, and resumes by
        re-prefilling prompt+generated (recompute, not swap — there is
        no host swap space worth the round-trip on TPU; with the radix
        tree the donated pages usually turn the recompute into a cache
        hit)."""
        pool = self.running + self.prefilling
        victim = max(pool, key=lambda r: r.arrival)
        if victim in self.running:
            self.running.remove(victim)
        else:
            self.prefilling.remove(victim)
        self._donate(victim)
        self.allocator.free_sequence(victim.seq)
        victim.seq = None
        victim.reserved_ahead = False
        victim.state = RequestState.WAITING
        victim.num_computed = 0
        victim.cached_tokens = 0
        victim.num_preemptions += 1
        self.num_preemptions += 1
        # preempted requests head the queue: FCFS by original arrival
        self.waiting.appendleft(victim)
        return victim

    # ---- slots reserved a step ahead -----------------------------------
    def reserve_ahead(self, reqs: List[Request]) -> bool:
        """Reserve NOW the slot each of `reqs` writes in the decode
        launch after the one in flight: what `schedule()` step 1 would
        do for them at the next step, done early so that the engine can
        enqueue that launch before it has fetched this one's tokens.
        Quiet or not at all: nobody is preempted and no page is copied
        for it. A dry free list is refilled from cached prefixes that no
        request uses, the ladder's first rung (as `_extend_slots` takes
        it for draft slots): a pool full of donated prefixes is a
        server's steady state, and dropping one decides nothing about
        live work. Where a slot needs more than that, what was reserved
        is given back and the answer is False: the next `schedule()`
        then reserves in its own order. It finds `reserved_ahead` on
        each request served here and appends nothing for it."""
        done: List[Request] = []
        for req in reqs:
            quiet = not self.allocator.append_copies(req.seq)
            while quiet:
                try:
                    self.allocator.append_token(req.seq)
                    break
                except BlocksExhausted:
                    quiet = self._reclaim(1)
            if not quiet:
                self.release_ahead(done)
                return False
            req.reserved_ahead = True
            done.append(req)
        return True

    def release_ahead(self, reqs: List[Request]):
        """Give back the slots `reserve_ahead` took for those of `reqs`
        that still hold one (a request that finished meanwhile gave its
        pages back whole)."""
        for req in reqs:
            if req.reserved_ahead:
                req.reserved_ahead = False
                self.allocator.truncate_sequence(
                    req.seq, req.seq.num_tokens - 1)

    # ---- the per-step decision ------------------------------------------
    def _window_chunk(self, req: Request, start: int, take: int) -> bool:
        """Hold the windowed layer groups' pages for the chunk
        [start, start + take) of `req` (the unbounded group's were taken
        at admission; a windowed group's are taken a chunk at a time and
        given back as the row advances). False where that pool is dry:
        it is sized never to be (`WindowGroup.pages_for`), so only an
        injected fault gets here, and the chunk waits a step."""
        try:
            self.allocator.advance_windows(req.seq, start, start + take)
        except BlocksExhausted:
            return False
        return True

    def schedule(self) -> ScheduleStep:
        preempted: List[Request] = []
        if self.allocator.windows:
            # a row between two chunks keeps its window and no more: what
            # its last chunk read from further back goes back first
            for req in self.prefilling:
                self.allocator.advance_windows(req.seq, req.num_computed,
                                               req.num_computed)

        # 1. guarantee every decoding request can append this step's
        #    token (may cross a page boundary); on pressure evict cached
        #    prefixes first, then the newest in-flight request. A request
        #    whose slot `reserve_ahead` took already has it.
        survivors: List[Request] = []
        for req in list(self.running):
            if req not in self.running:
                continue               # evicted by an earlier iteration
            if req.reserved_ahead:
                req.reserved_ahead = False
                req.pending_copies = []
                survivors.append(req)
                continue
            while True:
                try:
                    copies = self.allocator.append_token(req.seq)
                    req.pending_copies = copies
                    survivors.append(req)
                    break
                except BlocksExhausted:
                    if self._reclaim(1):
                        continue
                    victim = self._preempt_one(keep=req)
                    preempted.append(victim)
                    if victim is req:
                        break
        decodes = [r for r in survivors if r in self.running]
        budget = self.token_budget - len(decodes) * self.decode_token_cost

        # 2. continue in-flight prefills FCFS: each gets at most one
        #    chunk per step, sized to the remaining budget.
        chunks: List[PrefillChunk] = []
        # (snapshot taken after step 1: preemption cannot mutate it here)
        for req in sorted(self.prefilling, key=lambda r: r.arrival):
            if budget <= 0:
                break
            n = len(req.resume_ids)
            take = min(budget, n - req.num_computed)
            if take <= 0 or not self._window_chunk(req, req.num_computed,
                                                   take):
                continue
            chunks.append(PrefillChunk(req, req.num_computed, take,
                                       req.num_computed + take == n,
                                       is_first=False))
            budget -= take

        # 3. admit waiting prompts FCFS while budget/slots/pages allow.
        #    A cached-prefix match shares its pages and shrinks what
        #    must be prefilled; the first chunk takes whatever budget is
        #    left (chunked prefill — no oversized-prompt special case).
        #    Headroom check only: a prompt must see pages for prompt
        #    tokens + 1 free, which makes an immediate post-prefill
        #    preemption unlikely but does NOT reserve the extra page —
        #    same-step admissions crossing a boundary together can still
        #    contend, and preemption (step 1) resolves it.
        while self.waiting and budget > 0 and \
                self.num_in_flight < self.max_batch_size:
            req = self.waiting[0]
            ids = req.resume_ids
            n = len(ids)
            mpages, m = [], 0
            if self.prefix_cache is not None:
                # host-tier promotion (ISSUE 17) is scheduled against
                # the same chunked-prefill budget a recompute of those
                # tokens would draw — one token is held back so the
                # admitted request can always take a non-empty first
                # chunk in this step
                promoted_before = getattr(self.prefix_cache,
                                          "num_promoted_pages", 0)
                mpages, m = self.prefix_cache.match(
                    adapter_prefix_key(ids, req.adapter_key),
                    promote_budget=budget - 1)
                budget -= (getattr(self.prefix_cache,
                                   "num_promoted_pages", promoted_before)
                           - promoted_before) * self.allocator.page_size
                if m >= n:
                    # full hit: the LAST token must still run through
                    # the model to produce the next-token logits
                    keep = (n - 1) // self.allocator.page_size
                    mpages, m = mpages[:keep], \
                        keep * self.allocator.page_size
            short = (self.allocator.pages_needed(n + 1) - len(mpages)
                     - self.allocator.num_free)
            if short > 0 and not self._reclaim(short, protect=mpages):
                break                  # no pages — decodes will drain/free
            try:
                req.seq = self.allocator.alloc_sequence_with_prefix(
                    n, mpages)
            except BlocksExhausted:
                break
            take = min(budget, n - m)
            if not self._window_chunk(req, m, take):
                self.allocator.free_sequence(req.seq)
                req.seq = None
                break
            self.waiting.popleft()
            req.state = RequestState.PREFILL
            req.num_computed = m
            req.cached_tokens = m
            self.prefilling.append(req)
            chunks.append(PrefillChunk(req, m, take, m + take == n,
                                       is_first=True))
            budget -= take
        return ScheduleStep(chunks, decodes, preempted)

    # ---- completion hooks (engine calls these) ---------------------------
    def on_prefilled(self, req: Request):
        """Last chunk processed and first token sampled: request joins
        the decode batch (unless that token already finished it)."""
        if req in self.prefilling:
            self.prefilling.remove(req)
        req.state = RequestState.DECODE
        self.running.append(req)
        self.running.sort(key=lambda r: r.arrival)

    def finish_handoff(self, req: Request) -> int:
        """Finish a just-prefilled request for cross-worker handoff
        (ISSUE 18): its computed pages donate to the radix tree exactly
        like any finish, and the return value is the block-aligned
        token count of the donated span — the single source for how
        many tokens of `prompt+output` the fleet's kv_pull can ship.
        0 when nothing donates (no prefix cache, or a sub-page
        prompt): the decode side then simply re-prefills."""
        ids = req.prompt_ids + req.output_ids
        n = min(req.num_computed, len(ids),
                req.seq.num_tokens if req.seq is not None else 0)
        full = (n // self.allocator.page_size) * self.allocator.page_size
        if self.prefix_cache is None:
            full = 0
        self.finish(req, "handoff", donate=True)
        return full

    def finish(self, req: Request, reason: str, donate: bool = True):
        if req in self.running:
            self.running.remove(req)
        if req in self.prefilling:
            self.prefilling.remove(req)
        if req.seq is not None:
            if donate:
                self._donate(req)
            self.allocator.free_sequence(req.seq)
            req.seq = None
        req.reserved_ahead = False
        req.state = RequestState.FINISHED
        req.finish_reason = reason

    def cancel(self, req: Request, reason: str,
               donate: bool = True) -> bool:
        """Cancel a request in ANY live state — queued, mid-prefill,
        decoding, or preempted-back-to-waiting. Pages are donated to the
        prefix cache (valid KV; `donate=False` for quarantine — poisoned
        KV must never enter the tree) and freed. Returns False when the
        request already finished."""
        if req.state is RequestState.FINISHED:
            return False
        try:
            self.waiting.remove(req)
        except ValueError:
            pass
        self.finish(req, reason, donate=donate)
        return True
