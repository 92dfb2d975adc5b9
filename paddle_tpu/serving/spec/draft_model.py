"""Draft-model proposer: a smaller causal LM drafts K tokens per step.

The classic two-model speculative setup (Leviathan et al., 2023;
vLLM's draft-model speculator): a cheap `LlamaForCausalLM`-protocol
model autoregressively proposes K continuation tokens which the target
then scores in one verify launch. TPU-shaped like the engine itself:
the draft model owns its OWN `BlockAllocator` + paged K/V caches in
the same (num_pages, KVH, page, D) block-table layout the kernels
expect, and all its device work runs through a small bucketed program
grid — a per-sequence catch-up CHUNK program (a "prefill" span of the
draft model's `paged_forward`, the engine's contract of
`models/paged.py`) plus a BATCHED greedy decode program (a "decode"
span) — so drafting never triggers unbounded recompilation either.

Drafting is greedy by design: a deterministic proposal is verified
with the one-hot rejection rule (accept draft d with probability
p_target(d); on rejection sample the renormalized remainder), which is
unbiased for ANY deterministic proposer — so the same verify program
serves both this and `NgramProposer`, and greedy-target acceptance is
exact longest-prefix matching.

Resilience contract (`Proposer` docstring): drafting is advisory, so
every failure here degrades to "no drafts this step" rather than
propagating into the engine step — a draft OOM truncates that
request's draft KV and skips it; a failure that consumed the donated
draft caches (the TPU hazard `ServingEngine._caches_alive` guards)
disables the proposer for the engine's lifetime, other errors retry
next round and disable only after 3 consecutive failures. A disable
is never silent: `disabled_reason` records why and a RuntimeWarning
fires (a missing speedup must be diagnosable).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ...core.autograd import no_grad
from ...core.tensor import Tensor
from ...jit.api import functional_call
from ...models.paged import PAGED_ENTRY, PagedSpan
from ..kv_cache import BlockAllocator, BlocksExhausted, PAD_PAGE
from .proposer import Proposer

__all__ = ["DraftModelProposer"]


class _DraftSeq:
    """Per-request draft cache state: the tokens whose K/V currently
    live in the draft pool, and the pages holding them."""

    __slots__ = ("seq", "tokens")

    def __init__(self, seq):
        self.seq = seq
        self.tokens: List[int] = []


class DraftModelProposer(Proposer):
    def __init__(self, draft_model, *, num_pages: int = 128,
                 page_size: int = 16,
                 prefill_buckets=None, batch_buckets=None,
                 pages_buckets=None):
        from ..engine import _bucket_for, _pow2_buckets  # no cycle: the
        # engine never imports serving.spec (proposers are passed in)
        self._bucket_for = _bucket_for
        cfg = draft_model.cfg
        self.model = draft_model
        self.num_layers = cfg.num_hidden_layers
        self.num_kv = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self._weights = {k: t._data
                         for k, t in draft_model.state_dict().items()}
        from ...kernels.paged_attention import check_supported_paged
        dtype = next(iter(self._weights.values())).dtype
        check_supported_paged(
            (1, cfg.num_attention_heads, self.head_dim),
            (self.num_pages, self.num_kv, self.page_size, self.head_dim),
            dtype)
        self.max_seq_len = min(int(cfg.max_position_embeddings),
                               (self.num_pages - 1) * self.page_size)
        max_pages_per_seq = -(-self.max_seq_len // self.page_size)
        self.prefill_buckets = sorted(
            prefill_buckets or _pow2_buckets(
                min(16, self.max_seq_len), self.max_seq_len))
        self.batch_buckets = sorted(batch_buckets or _pow2_buckets(1, 8))
        self.pages_buckets = sorted(
            pages_buckets or _pow2_buckets(
                min(2, max_pages_per_seq), max_pages_per_seq))
        self.max_seq_len = min(self.max_seq_len,
                               self.pages_buckets[-1] * self.page_size)

        self.allocator = BlockAllocator(self.num_pages, self.page_size)
        shape = (self.num_pages, self.num_kv, self.page_size, self.head_dim)
        self._k_caches = [jnp.zeros(shape, dtype)
                          for _ in range(self.num_layers)]
        self._v_caches = [jnp.zeros(shape, dtype)
                          for _ in range(self.num_layers)]
        # unified ProgramCache (ISSUE 8): the draft model's catch-up
        # chunk + decode programs are their own families, bounded by
        # the draft bucket grid exactly like the engine's
        from ..program_cache import ProgramCache
        self.programs = ProgramCache()
        self.programs.register_family(
            "draft_chunk", lambda: (len(self.prefill_buckets)
                                    * len(self.pages_buckets)))
        self.programs.register_family(
            "draft_decode", lambda: (len(self.batch_buckets)
                                     * len(self.pages_buckets)))
        self._donate = (1, 2) if jax.default_backend() == "tpu" else ()
        # draft-model structure rides every draft program key (B1): a
        # program is traced over num_layers cache pairs, so two
        # proposers of different depth must never share one
        self._dkey = (("layers", self.num_layers),)
        self._states: Dict[int, _DraftSeq] = {}
        # drafting turned itself off (see propose()): the engine keeps
        # decoding plainly. `disabled_reason` records why — a silently
        # missing speedup must be diagnosable from the proposer state.
        self.disabled = False
        self.disabled_reason: str = ""
        self.num_draft_launches = 0
        self.num_propose_failures = 0
        self._consecutive_failures = 0

    # ------------------------------------------------------------ programs
    @property
    def num_compiled_programs(self) -> int:
        return self.programs.num_programs

    def program_counts(self):
        return self.programs.counts()

    def max_program_count(self, family=None) -> int:
        return self.programs.max_count(family)

    def _get_program(self, key, builder):
        return self.programs.get(key, builder)

    def _paged_program(self, body):
        """The frame of the draft model's two programs around
        `body(st, paged, *inputs) -> (greedy token(s), caches)`: the
        state wrapped in Tensors, the two cache lists as per-layer
        (k, v) views and back, jitted with both lists donated."""
        def program(state, kcs, vcs, *inputs):
            st = {k: Tensor(v) for k, v in state.items()}
            paged = [(Tensor(k), Tensor(v)) for k, v in zip(kcs, vcs)]
            toks, caches = body(st, paged, *inputs)
            return (toks, [c[0]._data for c in caches],
                    [c[1]._data for c in caches])

        # tpu-lint: cache-key-ok (donation is backend-constant per process)
        return jax.jit(program, donate_argnums=self._donate)

    def _build_chunk(self, S, P):
        """Catch-up chunk: write one span of ONE sequence's history into
        the draft cache and return the greedy next token (the first
        draft, when the span reaches the history end)."""
        # tpu-lint: cache-key-ok (per-proposer cache, no disk tier)
        model = self.model

        def body(st, paged, ids, cache_len, live, bt):
            logits, caches, _ = functional_call(
                model, st, Tensor(ids), paged, Tensor(bt),
                PagedSpan("prefill", Tensor(cache_len), Tensor(live)),
                method=PAGED_ENTRY)
            return jnp.argmax(logits._data[0, 0]).astype(jnp.int32), caches

        return self._paged_program(body)

    def _build_decode(self, B, P):
        """One batched greedy draft step over the draft paged caches."""
        # tpu-lint: cache-key-ok (per-proposer cache, no disk tier)
        model = self.model

        def body(st, paged, ids, bt, sl):
            logits, caches, _ = functional_call(
                model, st, Tensor(ids), paged, Tensor(bt),
                PagedSpan("decode", Tensor(sl)), method=PAGED_ENTRY)
            return jnp.argmax(logits._data[:, 0, :], axis=-1).astype(
                jnp.int32), caches

        return self._paged_program(body)

    # ------------------------------------------------------------- helpers
    def _state_of(self, req) -> _DraftSeq:
        st = self._states.get(req.request_id)
        if st is None:
            seq = self.allocator.alloc_sequence(0)
            st = _DraftSeq(seq)
            self._states[req.request_id] = st
        return st

    def _extend(self, st: _DraftSeq, n: int) -> bool:
        """Grow the draft sequence by n token slots; all-or-nothing (a
        mid-loop pool exhaustion rolls back to the entry length)."""
        base = st.seq.num_tokens
        try:
            for _ in range(n):
                # no forks in the draft pool -> never returns CoW copies
                self.allocator.append_token(st.seq)
        except BlocksExhausted:
            self.allocator.truncate_sequence(st.seq, base)
            return False
        return True

    def _sync(self, st: _DraftSeq, hist: List[int]):
        """Roll the draft cache back to its longest still-valid prefix
        of `hist` (stale tokens = rejected drafts or divergence), capped
        at len(hist)-1 so the catch-up chunk always has at least the
        newest token to process (its logits seed the first draft)."""
        lcp = 0
        for a, b in zip(st.tokens, hist):
            if a != b:
                break
            lcp += 1
        lcp = min(lcp, len(hist) - 1)
        if lcp < st.seq.num_tokens:
            self.allocator.truncate_sequence(st.seq, lcp)
        del st.tokens[lcp:]

    def _launch(self, prog, *args):
        self.num_draft_launches += 1
        with no_grad():
            return prog(self._weights, self._k_caches, self._v_caches,
                        *args)

    # ------------------------------------------------------------- propose
    def propose(self, reqs, k: int) -> List[List[int]]:
        drafts: List[List[int]] = [[] for _ in reqs]
        if self.disabled or k <= 0:
            return drafts
        try:
            out = self._propose(reqs, k, drafts)
            self._consecutive_failures = 0
            return out
        except Exception as exc:                         # noqa: BLE001
            # advisory contract: NO draft-side failure may take the
            # engine step down. Two bins: (a) the failed dispatch may
            # have consumed the donated draft caches (TPU) — nothing
            # valid to re-pass, same hazard as engine._caches_alive, so
            # drafting is off for this engine's life; (b) the caches
            # are alive (host-side error, pre-dispatch failure) — skip
            # this round and only give up after repeated failures.
            # Either way the shutdown is RECORDED, never silent.
            self.num_propose_failures += 1
            self._consecutive_failures += 1
            caches_dead = any(
                getattr(a, "is_deleted", lambda: False)()
                for a in (self._k_caches[0], self._v_caches[0]))
            if caches_dead:
                self._disable(f"draft launch consumed donated caches: "
                              f"{exc!r}")
            elif self._consecutive_failures >= 3:
                self._disable(f"3 consecutive propose failures, "
                              f"last: {exc!r}")
            return [[] for _ in reqs]

    def _disable(self, reason: str):
        import warnings
        self.disabled = True
        self.disabled_reason = reason
        warnings.warn(f"DraftModelProposer disabled ({reason}); the "
                      "engine continues with plain decode",
                      RuntimeWarning, stacklevel=3)

    def _propose(self, reqs, k, drafts):
        # --- per-request catch-up: prefill the history gap ---------------
        active = []                      # (row index, draft-state) pairs
        for i, req in enumerate(reqs):
            hist = [int(t) for t in req.resume_ids]
            if len(hist) + k - 1 > self.max_seq_len:
                continue                 # request outgrew the draft pool
            st = self._state_of(req)
            self._sync(st, hist)
            need = hist[len(st.tokens):]
            if not self._extend(st, len(need)):
                continue                 # draft pool dry: skip this one
            pos = len(st.tokens)
            tok = None
            while need:
                span = need[:self.prefill_buckets[-1]]
                S = self._bucket_for(len(span), self.prefill_buckets)
                P = self._bucket_for(
                    self.allocator.pages_needed(pos + len(span)),
                    self.pages_buckets)
                prog = self._get_program(
                    ("draft_chunk", S, P) + self._dkey,
                    lambda: self._build_chunk(S, P))
                bt = np.full((P,), PAD_PAGE, np.int32)
                npages = min(len(st.seq.pages), P)
                bt[:npages] = st.seq.pages[:npages]
                padded = np.zeros((1, S), np.int32)
                padded[0, :len(span)] = span
                tok, self._k_caches, self._v_caches = self._launch(
                    prog, jnp.asarray(padded), jnp.int32(pos),
                    jnp.int32(len(span)), jnp.asarray(bt))
                st.tokens.extend(span)
                pos += len(span)
                need = need[len(span):]
            drafts[i] = [int(tok)]
            active.append((i, st))

        # --- batched greedy decode for drafts 2..k -----------------------
        for _ in range(1, k):
            step = [(i, st) for i, st in active
                    if self._extend(st, 1)]
            if not step:
                break
            B = self._bucket_for(len(step), self.batch_buckets)
            maxp = max(len(st.seq.pages) for _, st in step)
            P = self._bucket_for(maxp, self.pages_buckets)
            prog = self._get_program(
                ("draft_decode", B, P) + self._dkey,
                lambda: self._build_decode(B, P))
            ids = np.zeros((B, 1), np.int32)
            sl = np.zeros((B,), np.int32)
            bt = np.full((B, P), PAD_PAGE, np.int32)
            for row, (i, st) in enumerate(step):
                ids[row, 0] = drafts[i][-1]
                sl[row] = st.seq.num_tokens
                bt[row, :len(st.seq.pages)] = st.seq.pages
            toks, self._k_caches, self._v_caches = self._launch(
                prog, jnp.asarray(ids), jnp.asarray(bt), jnp.asarray(sl))
            toks = np.asarray(toks)
            for row, (i, st) in enumerate(step):
                st.tokens.append(int(ids[row, 0]))   # its K/V just wrote
                drafts[i].append(int(toks[row]))
            active = step
        return drafts

    # ------------------------------------------------------------ cleanup
    def on_finished(self, req):
        st = self._states.pop(req.request_id, None)
        if st is not None:
            self.allocator.free_sequence(st.seq)

    def reset(self):
        for st in self._states.values():
            self.allocator.free_sequence(st.seq)
        self._states.clear()
