"""Paged KV-cache block management for the serving engine.

Host-side bookkeeping only: pages are integer ids into the device-side
(num_pages, KVH, page_size, D) cache arrays owned by the engine; this
module decides WHICH page holds WHICH tokens. Design follows the
block-based KV management of vLLM/PagedAttention (Kwon et al., SOSP '23):
fixed-size pages, a free list, per-page reference counts so a forked
prefix shares pages copy-on-write.

Kernel contract (kernels/paged_attention.py): page 0 is the reserved pad
page — block-table slots past a sequence's live pages must hold a valid
page id, and 0 is the designated one (reads of it are masked by
seq_lens). The allocator therefore never hands out page 0.
"""
from __future__ import annotations

import struct
import zlib
from collections import deque
from typing import Dict, List, Tuple

import numpy as np

from ..utils import faults

__all__ = ["BlockAllocator", "KVSequence", "WindowGroup", "BlocksExhausted",
           "PAD_PAGE",
           "HostPageStore", "HostPagesExhausted", "HostPageError",
           "HostPageCorrupt", "HostPageSlow", "HostPageLost",
           "encode_page_payload", "decode_page_payload"]

PAD_PAGE = 0

# Fault-injection point (ISSUE 3): an armed spec makes _alloc_page raise
# BlocksExhausted as if the pool were dry — the scheduler must degrade
# through its reclamation ladder (radix LRU eviction, then
# preempt-by-eviction), never crash or leak.
FAULT_ALLOC = faults.register_point("serving.kv.alloc_page")

# Fault-injection points (ISSUE 17): the host spill tier's read path.
# Each degrades a promotion into recompute-from-radix-prefix — the
# engine's outputs must stay bit-identical in all three cases, only the
# cached-token accounting changes.
FAULT_HOST_CORRUPT = faults.register_point("host_spill.corrupt")
FAULT_HOST_SLOW = faults.register_point("host_spill.slow")
FAULT_HOST_LOST = faults.register_point("host_spill.lost")


class BlocksExhausted(Exception):
    """No free page — the scheduler turns this into a preemption."""


class KVSequence:
    """One sequence's view of the cache: ordered page ids + token count.
    Page j covers token positions [j*page_size, (j+1)*page_size).

    `pages` are those of the unbounded layer group. Where the allocator
    has windowed groups (`WindowGroup`), `windows[g]` is group g's list
    at the SAME absolute positions, PAD_PAGE where a page was given back:
    slots [window_first[g], len(windows[g])) are held, every slot before
    them is PAD_PAGE."""

    __slots__ = ("pages", "num_tokens", "freed", "windows", "window_first")

    def __init__(self, n_windows: int = 0):
        self.pages: List[int] = []
        self.num_tokens = 0
        self.freed = False
        self.windows: List[List[int]] = [[] for _ in range(n_windows)]
        self.window_first: List[int] = [0] * n_windows

    def num_pages(self):
        return len(self.pages)

    def window_held(self, g: int = 0) -> int:
        """Pages group g holds for this sequence."""
        return len(self.windows[g]) - self.window_first[g]


class WindowGroup:
    """The pages of the layers that attend to a WINDOW: a query at
    position q reads the keys q - window < j <= q, so a page that lies
    wholly behind every query still to come is given back as the row
    advances, not at its end. The group has a pool of its own (its page
    ids name rows of ITS layers' arrays, page 0 its pad page), sized by
    `pages_for` from what the engine already knows, because a row's need
    is bounded.

    One token of slack: with the next query at q the group keeps the
    pages from position q - window on, not q - window + 1, so that the
    slot a launch ahead reserved can be given back (`truncate_sequence`
    by one token) and the row's last query run again. window + 1 tokens
    span at most window / page + 1 pages, the bound of `window` tokens,
    so the slack costs no page."""

    def __init__(self, num_pages: int, page_size: int, window: int):
        if window < 1:
            raise ValueError(f"window {window} must be positive")
        self.window = int(window)
        self.pool = BlockAllocator(num_pages, page_size)
        self.pages_released = 0      # given back as rows advanced

    def first_kept(self, first_query: int) -> int:
        """The first page a row keeps when its next query sits at
        `first_query`."""
        return max(0, first_query - self.window) // self.pool.page_size

    @staticmethod
    def pages_for(window: int, page_size: int, rows: int,
                  chunk_tokens: int) -> int:
        """Pages that never run out under `rows` rows in flight and
        `chunk_tokens` of prefill chunks a step: each row keeps at most
        window / page + 1 pages between launches, a step's chunks add at
        most their tokens' pages and one a chunk, and page 0 is the pad
        page."""
        per_row = -(-window // page_size) + 1
        return rows * (per_row + 1) + -(-chunk_tokens // page_size) + 1


class BlockAllocator:
    """Ref-counted page allocator over `num_pages` fixed-size pages.

    Invariant (checked by the property tests): every page is either in
    the free list with refcount 0 or held by >= 1 sequences with a
    positive refcount — never both, never negative.
    """

    def __init__(self, num_pages: int, page_size: int, windows=()):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the pad page)")
        if page_size <= 0 or page_size % 8 != 0:
            # the Pallas kernel needs sublane-tiled pages
            raise ValueError(f"page_size {page_size} must be a positive "
                             "multiple of 8")
        self.num_pages = num_pages
        self.page_size = page_size
        # FIFO free list: steady-state serving cycles through HBM pages
        # instead of hammering the most recently freed ones
        self._free = deque(range(1, num_pages))
        self._refs: Dict[int, int] = {}
        # the windowed layer groups riding on this (unbounded) group's
        # sequences; empty for a model of one group
        self.windows: List[WindowGroup] = list(windows)
        if any(g.pool.page_size != page_size for g in self.windows):
            raise ValueError("every layer group shares one page size")

    # ---- low-level page ops ---------------------------------------------
    def _alloc_page(self) -> int:
        if faults.fire(FAULT_ALLOC) is not None:
            raise BlocksExhausted("injected allocator OOM")
        if not self._free:
            raise BlocksExhausted(
                f"all {self.num_pages - 1} KV pages in use")
        pid = self._free.popleft()
        self._refs[pid] = 1
        return pid

    def _incref(self, pid: int):
        self._refs[pid] += 1

    def _decref(self, pid: int):
        r = self._refs.get(pid)
        if r is None or r <= 0:
            raise RuntimeError(f"double free of page {pid}")
        if r == 1:
            del self._refs[pid]
            self._free.append(pid)
        else:
            self._refs[pid] = r - 1

    # ---- occupancy -------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def occupancy(self) -> float:
        return self.num_used / float(self.num_pages - 1)

    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    def can_allocate(self, num_tokens: int) -> bool:
        return self.pages_needed(num_tokens) <= self.num_free

    # ---- sequence API ----------------------------------------------------
    def _alloc_pages(self, n: int) -> List[int]:
        """n fresh pages, all-or-nothing: a mid-loop BlocksExhausted
        (possible via the injected-OOM fault even after a num_free
        pre-check) rolls the partial allocation back before re-raising,
        so no page ever leaks with a refcount and no owner."""
        got: List[int] = []
        try:
            for _ in range(n):
                got.append(self._alloc_page())
        except BlocksExhausted:
            for pid in got:
                self._decref(pid)
            raise
        return got

    def alloc_sequence(self, num_tokens: int) -> KVSequence:
        """Pages for `num_tokens` tokens (a prompt about to prefill).
        All-or-nothing: on exhaustion nothing is held."""
        need = self.pages_needed(num_tokens)
        if need > self.num_free:
            raise BlocksExhausted(
                f"need {need} pages, {self.num_free} free")
        seq = KVSequence(len(self.windows))
        seq.pages = self._alloc_pages(need)
        seq.num_tokens = num_tokens
        return seq

    def alloc_sequence_with_prefix(self, num_tokens,
                                   prefix_pages) -> KVSequence:
        """Pages for `num_tokens` tokens whose first
        len(prefix_pages) * page_size tokens are already cached: the
        prefix pages are SHARED (refcounts bumped — the radix tree or a
        donor sequence keeps its own refs) and only the remainder is
        freshly allocated. All-or-nothing like alloc_sequence."""
        need = self.pages_needed(num_tokens)
        if len(prefix_pages) > need:
            raise ValueError(
                f"prefix of {len(prefix_pages)} pages exceeds the "
                f"{need} pages {num_tokens} tokens need")
        fresh = need - len(prefix_pages)
        if fresh > self.num_free:
            raise BlocksExhausted(
                f"need {fresh} fresh pages, {self.num_free} free")
        seq = KVSequence(len(self.windows))
        for pid in prefix_pages:
            self._incref(pid)
        try:
            fresh_pages = self._alloc_pages(fresh)
        except BlocksExhausted:
            for pid in prefix_pages:   # all-or-nothing: drop shared refs
                self._decref(pid)
            raise
        seq.pages = list(prefix_pages) + fresh_pages
        seq.num_tokens = num_tokens
        return seq

    def append_token(self, seq: KVSequence) -> List[Tuple[int, int]]:
        """Grow `seq` by one token, returning the (src_page, dst_page)
        device copies the caller must perform (copy-on-write when the
        written page is shared with a fork; empty list otherwise)."""
        if seq.freed:
            raise RuntimeError("append to a freed sequence")
        copies: List[Tuple[int, int]] = []
        pos = seq.num_tokens
        if self.windows:
            # first: on exhaustion there nothing of this group has moved
            self.advance_windows(seq, pos, pos + 1)
        j = pos // self.page_size
        if j == len(seq.pages):            # crossing into a new page
            seq.pages.append(self._alloc_page())
        else:
            pid = seq.pages[j]
            if self._refs[pid] > 1:        # shared with a fork: CoW
                new = self._alloc_page()
                self._decref(pid)
                seq.pages[j] = new
                copies.append((pid, new))
        seq.num_tokens = pos + 1
        return copies

    def append_copies(self, seq: KVSequence) -> bool:
        """Whether the next `append_token` would copy a page (the slot
        it writes lies in a page shared with a fork)."""
        j = seq.num_tokens // self.page_size
        return j < len(seq.pages) and self._refs[seq.pages[j]] > 1

    def truncate_sequence(self, seq: KVSequence, num_tokens: int):
        """Shrink `seq` to its first `num_tokens` tokens, releasing the
        pages that covered only the dropped tail — the speculative-
        decoding KV ROLLBACK: rejected draft tokens' pages return to
        the free list; the page holding the last surviving token stays
        (its dead tail slots are masked by seq_lens, the same contract
        as any partially-filled page).

        Invariants preserved by construction: releases go through
        `_decref`, so a dropped page shared with a fork or held by the
        radix tree (donated while this sequence still lived) merely
        loses this sequence's ref — CoW bookkeeping and tree refs stay
        exact, and `check_invariants` holds after any truncation.
        `num_tokens=0` is legal (all pages released, sequence still
        usable/growable — unlike `free_sequence` it is NOT terminal).
        """
        if seq.freed:
            raise RuntimeError("truncate of a freed sequence")
        num_tokens = int(num_tokens)
        if not 0 <= num_tokens <= seq.num_tokens:
            raise ValueError(
                f"truncate to {num_tokens} outside [0, {seq.num_tokens}]")
        keep = self.pages_needed(num_tokens)
        dropped = seq.pages[keep:]
        del seq.pages[keep:]
        for pid in dropped:
            self._decref(pid)
        for g, group in enumerate(self.windows):
            pages = seq.windows[g]
            for pid in pages[max(keep, seq.window_first[g]):]:
                group.pool._decref(pid)
            del pages[keep:]
            seq.window_first[g] = min(seq.window_first[g], len(pages))
        seq.num_tokens = num_tokens

    # ---- windowed layer groups ------------------------------------------
    def advance_windows(self, seq: KVSequence, first_query: int, end: int):
        """Make every windowed group ready for a launch whose queries sit
        at positions first_query .. end - 1: give back the pages that lie
        wholly behind `first_query - window` (`WindowGroup.first_kept`),
        then hold a page for every position up to `end`. Does again what
        was done for nothing (a retry after BlocksExhausted finds the
        pages it got), and with end == first_query only gives back."""
        need = self.pages_needed(end)
        for g, group in enumerate(self.windows):
            pages = seq.windows[g]
            lo = min(group.first_kept(first_query), need)
            for j in range(seq.window_first[g], min(lo, len(pages))):
                group.pool._decref(pages[j])
                pages[j] = PAD_PAGE
                group.pages_released += 1
            pages.extend([PAD_PAGE] * (lo - len(pages)))
            seq.window_first[g] = max(seq.window_first[g], lo)
            if need > len(pages):
                pages.extend(group.pool._alloc_pages(need - len(pages)))

    def fork_sequence(self, seq: KVSequence) -> KVSequence:
        """Prefix fork: the child shares every page (refcounts bumped);
        the first divergent append to a shared page triggers CoW."""
        if seq.freed:
            raise RuntimeError("fork of a freed sequence")
        if self.windows:
            raise NotImplementedError(
                "fork of a sequence with windowed layer groups")
        child = KVSequence()
        child.pages = list(seq.pages)
        child.num_tokens = seq.num_tokens
        for pid in child.pages:
            self._incref(pid)
        return child

    def free_sequence(self, seq: KVSequence):
        if seq.freed:
            raise RuntimeError("double free of sequence")
        for pid in seq.pages:
            self._decref(pid)
        for g, group in enumerate(self.windows):
            for pid in seq.windows[g][seq.window_first[g]:]:
                group.pool._decref(pid)
            seq.windows[g], seq.window_first[g] = [], 0
        seq.pages = []
        seq.num_tokens = 0
        seq.freed = True

    # ---- kernel-facing tensors ------------------------------------------
    def block_table(self, seqs, max_pages: int, group: int = 0) -> np.ndarray:
        """(B, max_pages) int32 block table; unused slots hold PAD_PAGE
        (the `paged_attention_decode` padding contract). `group` 0 is
        this allocator's own; g > 0 is windowed group g - 1's, at the
        same positions, PAD_PAGE where a page was given back."""
        bt = np.full((len(seqs), max_pages), PAD_PAGE, np.int32)
        for i, s in enumerate(seqs):
            pages = s.pages if group == 0 else s.windows[group - 1]
            if len(pages) > max_pages:
                raise ValueError(
                    f"sequence holds {len(pages)} pages > table width "
                    f"{max_pages}")
            bt[i, :len(pages)] = pages
        return bt

    def seq_lens(self, seqs) -> np.ndarray:
        return np.asarray([s.num_tokens for s in seqs], np.int32)

    def check_invariants(self):
        """Debug/test hook: free list and refcounts partition the pages."""
        free = set(self._free)
        held = set(self._refs)
        assert not (free & held), f"pages both free and held: {free & held}"
        assert all(r > 0 for r in self._refs.values())
        assert PAD_PAGE not in free and PAD_PAGE not in held
        assert len(free) + len(held) == self.num_pages - 1
        for group in self.windows:
            group.pool.check_invariants()


# ---------------------------------------------------------------------------
# Host spill tier (ISSUE 17): pinned host-RAM pages under the radix cache.
# ---------------------------------------------------------------------------

class HostPagesExhausted(Exception):
    """No free host page — the radix cache falls back to dropping."""


class HostPageError(Exception):
    """A host page read failed; promotion degrades to recompute."""


class HostPageCorrupt(HostPageError):
    """Payload failed its CRC — the stored bytes are untrustworthy."""


class HostPageSlow(HostPageError):
    """The host read missed its deadline; the page itself is intact."""


class HostPageLost(HostPageError):
    """The backing host buffer is gone (e.g. reclaimed by the OS)."""


# Page-payload wire format. One payload carries ONE radix page's KV bytes
# across every layer (k row, v row, plus the int8 scale rows when the
# cache is quantized). The same bytes are the demote/promote unit AND the
# PR-14 mailbox frame body for cross-worker prefix pulls, so corruption
# detection must be real: the header carries a CRC32 of the body and
# decode refuses anything that does not check out.
PAYLOAD_MAGIC = b"KVPG"
PAYLOAD_VERSION = 1
_PAYLOAD_HEADER = struct.Struct(">4sBHI")   # magic, version, n_arrays, crc


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # bfloat16 etc. live in ml_dtypes (a jax dependency), not numpy
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def encode_page_payload(arrays) -> bytes:
    """Serialize a list of ndarrays (one page's per-layer rows) into a
    self-describing CRC-protected byte string."""
    parts: List[bytes] = []
    for a in arrays:
        a = np.ascontiguousarray(a)
        dt = str(a.dtype).encode("ascii")
        parts.append(struct.pack(">B", len(dt)))
        parts.append(dt)
        parts.append(struct.pack(">B", a.ndim))
        parts.append(struct.pack(f">{a.ndim}I", *a.shape))
        raw = a.tobytes()
        parts.append(struct.pack(">I", len(raw)))
        parts.append(raw)
    body = b"".join(parts)
    head = _PAYLOAD_HEADER.pack(PAYLOAD_MAGIC, PAYLOAD_VERSION,
                                len(arrays), zlib.crc32(body) & 0xFFFFFFFF)
    return head + body


def decode_page_payload(buf: bytes) -> List[np.ndarray]:
    """Inverse of encode_page_payload. Raises HostPageCorrupt on any
    structural or CRC mismatch — a corrupt page must never reach the
    device arrays."""
    if len(buf) < _PAYLOAD_HEADER.size:
        raise HostPageCorrupt("payload truncated before header")
    magic, version, n_arrays, crc = _PAYLOAD_HEADER.unpack_from(buf)
    if magic != PAYLOAD_MAGIC or version != PAYLOAD_VERSION:
        raise HostPageCorrupt(f"bad payload header {magic!r} v{version}")
    body = buf[_PAYLOAD_HEADER.size:]
    if zlib.crc32(body) & 0xFFFFFFFF != crc:
        raise HostPageCorrupt("payload CRC mismatch")
    arrays: List[np.ndarray] = []
    off = 0
    try:
        for _ in range(n_arrays):
            (dlen,) = struct.unpack_from(">B", body, off); off += 1
            dtype = _np_dtype(body[off:off + dlen].decode("ascii"))
            off += dlen
            (ndim,) = struct.unpack_from(">B", body, off); off += 1
            shape = struct.unpack_from(f">{ndim}I", body, off)
            off += 4 * ndim
            (nbytes,) = struct.unpack_from(">I", body, off); off += 4
            raw = body[off:off + nbytes]
            off += nbytes
            if len(raw) != nbytes:
                raise HostPageCorrupt("payload truncated inside array")
            arrays.append(np.frombuffer(raw, dtype).reshape(shape).copy())
    except (struct.error, ValueError) as e:
        raise HostPageCorrupt(f"payload structure invalid: {e}") from None
    if off != len(body):
        raise HostPageCorrupt(f"{len(body) - off} trailing payload bytes")
    return arrays


class HostPageStore:
    """Ref-counted host-RAM page pool: the spill tier's analogue of
    BlockAllocator, holding encoded page payloads instead of device
    rows. Ids are dense ints over `num_pages` slots with the same
    free-list/refcount discipline (no pad page — host ids never reach
    a device block table).

    The read path (`get`) is where the host_spill fault points live:
    `lost` fires before the lookup (the buffer is gone — the store
    forgets it too, so recovery matches reality), `slow` models a
    deadline miss on an intact page, and `corrupt` flips a body byte so
    decode_page_payload's CRC check — not the injection site — is what
    detects it.
    """

    def __init__(self, num_pages: int):
        if num_pages <= 0:
            raise ValueError("host spill pool needs >= 1 page")
        self.num_pages = num_pages
        self._free = deque(range(num_pages))
        self._refs: Dict[int, int] = {}
        self._payloads: Dict[int, bytes] = {}
        self.bytes_stored = 0

    # ---- page ops --------------------------------------------------------
    def put(self, payload: bytes) -> int:
        if not self._free:
            raise HostPagesExhausted(
                f"all {self.num_pages} host pages in use")
        hid = self._free.popleft()
        self._refs[hid] = 1
        self._payloads[hid] = bytes(payload)
        self.bytes_stored += len(payload)
        return hid

    def get(self, hid: int) -> bytes:
        if faults.fire(FAULT_HOST_LOST) is not None:
            self._forget(hid)
            raise HostPageLost(f"host page {hid} backing buffer gone")
        if self._refs.get(hid, 0) <= 0:
            raise KeyError(f"host page {hid} not held")
        if faults.fire(FAULT_HOST_SLOW) is not None:
            raise HostPageSlow(f"host page {hid} read missed deadline")
        payload = self._payloads[hid]
        if faults.fire(FAULT_HOST_CORRUPT) is not None:
            payload = payload[:-1] + bytes([payload[-1] ^ 0xFF])
        return payload

    def _forget(self, hid: int):
        """Lost-page recovery: drop the slot entirely regardless of
        refcount (the holder's decref path is bypassed — the caller
        drops its radix node instead)."""
        if hid in self._refs:
            del self._refs[hid]
            self.bytes_stored -= len(self._payloads.pop(hid))
            self._free.append(hid)

    def incref(self, hid: int):
        self._refs[hid] += 1

    def decref(self, hid: int):
        r = self._refs.get(hid)
        if r is None or r <= 0:
            raise RuntimeError(f"double free of host page {hid}")
        if r == 1:
            del self._refs[hid]
            self.bytes_stored -= len(self._payloads.pop(hid))
            self._free.append(hid)
        else:
            self._refs[hid] = r - 1

    def holds(self, hid: int) -> bool:
        """True iff the store still holds `hid` (a lost-fault recovery
        may have forgotten it out from under its holders)."""
        return self._refs.get(hid, 0) > 0

    # ---- occupancy -------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_pages - len(self._free)

    def occupancy(self) -> float:
        return self.num_used / float(self.num_pages)

    def check_invariants(self):
        free = set(self._free)
        held = set(self._refs)
        assert not (free & held), f"host pages free AND held: {free & held}"
        assert all(r > 0 for r in self._refs.values())
        assert held == set(self._payloads), "payloads out of sync with refs"
        assert len(free) + len(held) == self.num_pages
        assert self.bytes_stored == \
            sum(len(p) for p in self._payloads.values())
