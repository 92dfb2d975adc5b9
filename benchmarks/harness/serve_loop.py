"""Drivers `closed_loop` and `open_loop`: one `ServingEngine` driven
through add_request()/step() by ONE thread, as the engine is meant to be
embedded. Every time is taken on this client's clock: a token's time is
when the `step()` that returned it came back, and an open-loop request is
timed from when it was DUE, not from when `add_request` took it.

  closed_loop  `clients` callers, each sending its next request when the
               last one finished; the window opens after `warm_seconds`
               once the batch has been full
  open_loop    arrivals on the generator's schedule at a fixed rate,
               whatever the engine does; the window opens after
               `warm_seconds` of that same traffic
"""
from __future__ import annotations

import gc
import time

import numpy as np

from . import loadgen, lookup
from .common import (CacheCounter, memory_peak_bytes, percentile, say,
                     within)
from .observe import Spans, delta

CHECK_SAMPLE = 4          # finished requests held against the reference:
#                           the longest, and the others drawn from the seed
FIRST_TOKEN_CAP_S = 30.0  # after the window, for requests due inside it


def setup(cfg: dict, cell: dict, seed: int):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine

    fam = lookup.family(cfg)
    pcfg = fam.config(cfg)
    paddle.seed(seed % (2 ** 31 - 1))
    model = fam.build_model(pcfg, cfg["dtype"])
    fam.load_weights(model, pcfg, cfg, seed)     # the benchmark's own
    jax.block_until_ready([p._data for p in model.parameters()])
    kw = dict(cfg["engine"])
    kw.update(cell.get("engine", {}))
    eng = ServingEngine(model, **kw)
    return pcfg, model, eng


def counters(eng, cache: CacheCounter) -> dict:
    build = {str(k[:3]): ms for k, ms in
             eng.programs.compile_times_ms().items() if ms is not None}
    return {"serving": dict(eng.metrics.counters),
            "programs": {"count": eng.num_compiled_programs,
                         "build_ms": build},
            "jax_cache": {"hits": cache.hits, "misses": cache.misses}}


class CollectorPauses:
    """A `gc.callbacks` entry: how often and for how long Python's
    collector held the driving thread, by generation. Read beside the
    window's longest steps, it says whether runs of equal work differ by
    the interpreter's pauses or by the machine standing still."""

    def __init__(self):
        self.t, self.n, self.s = None, [0, 0, 0], [0.0, 0.0, 0.0]

    def __call__(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            g = info["generation"]
            self.n[g] += 1
            self.s[g] += time.perf_counter() - self.t


class Source:
    """Which requests are due. Open loop: by the schedule. Closed loop:
    one a client, the next when its last has finished (due then: the
    caller stamps it)."""

    def __init__(self, requests, clients=None):
        self.requests, self.clients = requests, clients
        self.next = 0
        self.idle = clients            # closed loop: clients with none out

    def due(self, now: float):
        out = []
        if self.clients is None:
            while self.next < len(self.requests) \
                    and self.requests[self.next].due <= now:
                out.append(self.requests[self.next])
                self.next += 1
        else:
            while self.idle > 0:
                r = self.requests[self.next % len(self.requests)]
                if self.next >= len(self.requests):    # the pool wraps
                    r = loadgen.Request(self.next, None, r.prompt, r.n_out)
                out.append(r)
                self.next += 1
                self.idle -= 1
        return out

    def next_due(self):
        if self.clients is None and self.next < len(self.requests):
            return self.requests[self.next].due
        return None

    def finished(self, req):
        if self.clients is not None:
            self.idle += 1


def warm_programs(eng, vocab: int, rng):
    """Launch every program the cell pins, one request at a time: one
    prompt a prefill bucket (a single chunk of that bucket) and two
    decode steps."""
    for s in eng.prefill_buckets:
        n = min(s, eng.max_seq_len - 3)
        eng.add_request(rng.integers(0, vocab, n).tolist(), max_new_tokens=3)
        while eng.has_work():
            eng.step()


class Traffic:
    """The client side of one engine: admits what is due, takes one engine
    step (or sleeps until the next arrival), and stamps every token with
    the client's clock when the step that returned it came back."""

    def __init__(self, eng, src: Source, spans: Spans, closed: bool):
        self.eng, self.src, self.spans, self.closed = eng, src, spans, closed
        self.by_rid, self.sent, self.steps = {}, [], []
        self.t0 = time.perf_counter()

    def tick(self):
        eng, src, clock = self.eng, self.src, time.perf_counter
        now = clock()
        with self.spans.span("bench.admit"):
            for r in src.due(now - self.t0):
                r.due = now if self.closed else self.t0 + r.due
                try:
                    r.rid = eng.add_request(r.prompt, max_new_tokens=r.n_out)
                    self.by_rid[r.rid] = r
                except (ValueError, RuntimeError) as e:  # refused at the door
                    r.refused = True
                    say(f"serve: request {r.idx} refused: {e!r}")
                    src.finished(r)
                r.t_admit = clock()
                self.sent.append(r)
        if eng.has_work():
            c = eng.metrics.counters
            chunks = c["prefill_chunks"]
            with self.spans.span("bench.engine_step"):
                out = eng.step()
            self.stamp(out, chunks)
        else:
            nxt = src.next_due()
            wait = 0.001 if nxt is None \
                else min(0.005, self.t0 + nxt - clock())
            if wait > 0:
                time.sleep(wait)

    def stamp(self, out, chunks_before=None):
        tn = time.perf_counter()
        for rid, tok in out:
            r = self.by_rid[rid]
            r.tokens.append(tok)
            r.t_tokens.append(tn)
            if len(r.tokens) == r.n_out:
                self.src.finished(r)
        if chunks_before is not None:
            self.steps.append((tn, len(out), self.eng.metrics.counters[
                "prefill_chunks"] - chunks_before))

    def cancel_all(self):
        """Abort what is still in flight and step until the engine is
        empty; then drop the prefix cache. Returns pages still in use."""
        for r in self.sent:
            if r.rid is not None and len(r.tokens) < r.n_out:
                self.eng.abort(r.rid)
        guard = 0
        while self.eng.has_work() and guard < 1000:
            self.eng.step()
            guard += 1
        self.eng.reset_prefix_cache()
        return self.eng.allocator.num_used


def run(cfg, cell, *, seed, seconds, cache, phases, tracer=None, spans=None):
    t = cell["traffic"]
    spans = spans or Spans()
    closed = cell["driver"] == "closed_loop"
    pcfg, model, eng = setup(cfg, cell, seed)
    phases.mark("model_build")
    rng = np.random.default_rng(seed)
    warm_programs(eng, cfg["vocab_size"], rng)
    programs_pinned = eng.num_compiled_programs
    phases.mark("program_build")

    warm_s = float(t["warm_seconds"])
    if closed:
        n_req, clients = int(t["pool"]), int(t["clients"])
    else:
        n_req = int(t["arrivals"]["rate"] * (warm_s + seconds) * 1.25) + 64
        clients = None
    reqs = loadgen.make_requests(t, seed, n_req, cfg["vocab_size"],
                                 eng.max_seq_len, period=seconds)
    src = Source(reqs, clients)
    say(f"serve: {n_req} requests drawn; prompt lengths "
        f"{loadgen.describe([len(r.prompt) for r in reqs])}, output lengths "
        f"{loadgen.describe([r.n_out for r in reqs])}, max_seq_len "
        f"{eng.max_seq_len}")
    if tracer is not None:
        tracer.warm()

    max_batch = eng.batch_buckets[-1]
    trace_s = float(t.get("trace_seconds", 3.0))
    ws = before = None           # the window's start, counters at it
    batch_full = not closed
    traced = False
    clock = time.perf_counter
    tr = Traffic(eng, src, spans, closed)
    sent, steps, t0 = tr.sent, tr.steps, tr.t0
    pauses = CollectorPauses()
    while True:
        now = clock()
        if ws is None and now - t0 >= warm_s and batch_full:
            ws, before = now, counters(eng, cache)
            gc.callbacks.append(pauses)
            spans.reset()
            phases.mark("warm_traffic")
        if ws is not None and now - ws >= seconds:
            break
        if tracer is not None and ws is not None and not traced \
                and now - ws >= seconds - trace_s:
            tracer.start()
            traced, slice_lo = True, clock()
        tr.tick()
        if not batch_full and len(eng.scheduler.running) >= max_batch:
            batch_full = True
    we = ws + seconds
    gc.callbacks.remove(pauses)
    after = counters(eng, cache)
    if traced:                   # the slice ends with the window
        slice_hi = clock()
        tracer.stop()
    phases.skip()
    # requests due inside the window still waiting for their first token
    cap = clock() + FIRST_TOKEN_CAP_S
    in_window = [r for r in sent if ws <= r.due < we]
    while eng.has_work() and clock() < cap and any(
            not r.t_tokens and not r.refused for r in in_window):
        tr.stamp(eng.step())

    peak = memory_peak_bytes()   # read before the reference runs beside it
    in_win = delta(after, before)

    # --------------------------------------------------- the window's numbers
    tok_t = np.asarray([x for r in sent for x in r.t_tokens])
    n_tokens = int(((tok_t >= ws) & (tok_t < we)).sum())
    gaps, ctx_slice = [], 0
    # the tokens processed: a prompt where its first token fell in the
    # window, a decoded token where it did. Over THESE: how many, the keys
    # each attended to, and how many came out of the head
    keys = processed = heads = 0
    if not traced:
        slice_lo = slice_hi = we
    for r in sent:
        tt, p = np.asarray(r.t_tokens), len(r.prompt)
        if tt.size and ws <= tt[0] < we:      # prefilled: token i saw i + 1
            keys, processed = keys + p * (p + 1) // 2, processed + p
            heads += 1                        # the prompt's last position
        if tt.size > 1:
            g = np.diff(tt)
            later = tt[1:]
            inside = (later >= ws) & (later < we)
            gaps.append(g[inside])
            # a decode step that emitted output j >= 1 read prompt + j tokens
            j = np.nonzero(inside)[0] + 1
            keys, processed = keys + int((p + j).sum()), processed + j.size
            heads += j.size
            j = np.nonzero((later >= slice_lo) & (later < slice_hi))[0] + 1
            ctx_slice += int((p + j).sum())
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    e2e = {"serve_tokens_per_s": (n_tokens / seconds, "tokens/s"),
           "itl_p95_ms": (percentile(gaps, 95) * 1e3, "ms")}
    failed = [r for r in in_window if r.refused or not r.t_tokens]
    if not closed:
        far = clock() - ws                       # beyond any limit
        ttft = [(r.t_tokens[0] - r.due) if r.t_tokens else far
                for r in in_window]
        e2e["ttft_p95_ms"] = (percentile(ttft, 95) * 1e3, "ms")
        ttft_ms = {f"ttft_p{q}_ms": percentile(ttft, q) * 1e3
                   for q in (50, 95)}
        say(f"serve: ttft over {len(ttft)} requests due in the window: "
            f"p50 {percentile(ttft, 50) * 1e3:.1f} ms, p95 "
            f"{percentile(ttft, 95) * 1e3:.1f} ms")
    late = [r.t_admit - r.due for r in in_window]
    win_steps = [s for s in steps if ws <= s[0] < we]
    say(f"serve: window {seconds}s: {n_tokens} tokens, {len(in_window)} "
        f"requests due/sent, {len(win_steps)} engine steps, {gaps.size} token "
        f"gaps (p50 {percentile(gaps, 50) * 1e3:.2f} ms); the generator "
        f"admitted late by p50 {percentile(late, 50) * 1e3:.2f} ms, p95 "
        f"{percentile(late, 95) * 1e3:.2f} ms; waiting queue at the window's "
        f"end {eng.scheduler.queue_depth}")
    # where runs of equal work differ: the longest steps, and the collector
    took = np.diff([s[0] for s in win_steps]) if len(win_steps) > 1 \
        else np.zeros(1)
    slow = took[took > 6 * np.median(took)]
    say(f"serve: steps took p50 {np.median(took) * 1e3:.2f} ms, p99 "
        f"{percentile(took, 99) * 1e3:.2f} ms, longest "
        f"{took.max() * 1e3:.1f} ms; {slow.size} over six times the median, "
        f"{slow.sum():.3f} s together; Python's collector ran "
        f"{pauses.n} times a generation and held the thread "
        f"{[round(x, 4) for x in pauses.s]} s")
    c = in_win["serving"]
    values = {"tokens_per_s": n_tokens / seconds, "steps": len(win_steps),
              "prefill_steps": sum(1 for s in win_steps if s[2] > 0),
              "requests": len(in_window), "window_s": seconds,
              "admit_late_p95_ms": percentile(late, 95) * 1e3,
              **({} if closed else ttft_ms),
              "slice_decode_context_tokens": ctx_slice if traced else None,
              "processed_tokens_per_s": processed / seconds,
              "mean_context_tokens": keys / processed if processed else None,
              "head_tokens_per_processed":
                  heads / processed if processed else None,
              # the engine's own count of the chunks and decode rows that
              # ran in the window: the same but for where a prompt that
              # straddles an edge of the window is counted
              "engine_processed_tokens_per_s":
                  (c["prefill_tokens"] + c["decode_tokens"]) / seconds,
              "queue_depth_end": eng.scheduler.queue_depth}

    # ------------------------------------------------------------ the check
    from paddle_tpu.serving.scheduler import RequestState
    short = [r for r in sent if r.rid is not None
             and (q := eng.requests.get(r.rid)) is not None
             and q.state is RequestState.FINISHED
             and len(r.tokens) != r.n_out]
    done = sorted((r for r in sent if len(r.tokens) == r.n_out),
                  key=lambda r: -(len(r.prompt) + r.n_out))
    others = rng.permutation(max(len(done) - 1, 0))[:CHECK_SAMPLE - 1]
    pick = done[:1] + [done[1 + i] for i in others]
    used = tr.cancel_all()        # the rest is not needed: cancel, not drain
    idle, pad_to = not eng.has_work(), eng.max_seq_len
    program_counts = eng.program_counts()
    eng.shutdown()
    tr.eng = eng = None           # the pool, the programs and the weights go
    fam = lookup.family(cfg)
    del model                     # ... and reads its own, from the seed
    gc.collect()
    w = fam.reference_weights(pcfg, cfg, seed)
    limits = fam.gap_limits(cfg)
    rows, gaps_all = [], []
    for r in pick:
        g, _ = fam.token_gaps(w, pcfg, r.prompt, r.tokens, pad_to=pad_to)
        gaps_all.append(np.asarray(g, np.float64))
        rows.append((r.idx, len(r.prompt), r.n_out, round(float(g.max()), 4),
                     round(float(g.mean()), 5), int((g > 0).sum()),
                     len(set(r.tokens))))
    gaps_all = np.concatenate(gaps_all) if gaps_all else np.full(1, np.nan)
    # over the sample's served tokens, how far each lies under the plain
    # reference's best token at its position: the mean (what a loss of
    # precision moves) and the widest (what one wrong token moves)
    checks = {"logit_gap_mean": (float(gaps_all.mean()), limits["mean"]),
              "logit_gap_widest": (float(gaps_all.max()), limits["widest"])}
    checks.update({
        "sample_missing": (CHECK_SAMPLE - len(pick), 0),
        "finished_requests_short_of_their_length": (len(short), 0),
        "programs_built_in_window": (
            in_win["programs"]["count"] + in_win["jax_cache"]["hits"]
            + in_win["jax_cache"]["misses"], 0),
        "programs_beyond_the_pinned":
            (after["programs"]["count"] - programs_pinned, 0),
        "pages_in_use_after_reset": (used + (0 if idle else 1), 0)})
    say(f"serve: reference check over {gaps_all.size} served tokens "
        f"(request, prompt, output, widest gap, mean gap, tokens under the "
        f"reference's best, distinct tokens served) {rows}; "
        f"programs {program_counts} built in "
        f"{ {k: round(v / 1e3, 1) for k, v in after['programs']['build_ms'].items()} } s; "
        f"pages in use after reset {used}")
    phases.mark("check")
    obs = {"values": values, "spans": spans.durations, "checks": checks,
           "memory_peak_bytes": peak,
           "counters": {"window": in_win, "process": after}}
    return e2e, obs, within(checks), len(in_window), len(failed)
