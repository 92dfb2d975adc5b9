"""The one general, seeded load generator. A traffic mix is DATA (the
`traffic` object of a `workloads/*.json`); this file is the only code
that reads it.

What the work IS comes from `traffic.shape_seed`, fixed in the cell file:
one CYCLE of (gap before arrival, prompt length, output length) slots. An
open loop's cycle lasts exactly the window (`period`): round(rate x
period) arrivals whose gaps are scaled to add up to it, repeated for as
long as the run lasts. `--seed` only ROTATES the cycle (which slot comes
first) and draws the token ids. So every seed's window holds the same
arrivals and lengths in another order, and runs with different seeds
differ no more than where the window cuts the cycle makes them.

traffic keys
  prompt, output   {"dist": "lognormal", "median", "sigma", "min", "max"} |
                   {"dist": "uniform", "min", "max"} | {"dist": "constant", "value"}
  repeat           each drawn prompt is sent this many times (default 1)
  shared_prefix    {"tokens": n, "tenants": k, "zipf": a}: a prompt starts
                   with its tenant's fixed n tokens (tenant by a Zipf law)
  arrivals         {"dist": "poisson", "rate"} | {"dist": "gamma", "rate", "cv"}
                   (open loop; `rate` is requests a second)
  clients, pool    closed loop: concurrent clients, and how many distinct
                   requests the run cycles through
  warm_seconds     traffic before the window opens
  batch, seq, zipf train loop: tokens a step and the law of the ids
"""
from __future__ import annotations

import math

import numpy as np


class Request:
    __slots__ = ("idx", "due", "prompt", "n_out", "rid", "t_admit",
                 "t_tokens", "tokens", "refused", "client")

    def __init__(self, idx, due, prompt, n_out):
        self.idx, self.due, self.prompt, self.n_out = idx, due, prompt, n_out
        self.rid = self.t_admit = self.client = None
        self.t_tokens, self.tokens, self.refused = [], [], False


def _rng(seed: int):
    return np.random.default_rng(int(seed))


def _draw(spec: dict, n: int, rng) -> np.ndarray:
    dist = spec["dist"]
    if dist == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"], n)
    elif dist == "constant":
        return np.full(n, int(spec["value"]), np.int64)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def draw_sizes(traffic: dict, n: int, max_seq_len: int):
    """The cycle's `n` (prompt length, output length) slots, from
    shape_seed. An output is trimmed where prompt + output would pass
    `max_seq_len`."""
    rng = _rng(traffic["shape_seed"])
    rep = int(traffic.get("repeat", 1))
    m = -(-n // rep)
    p = np.repeat(_draw(traffic["prompt"], m, rng), rep)[:n]
    o = np.repeat(_draw(traffic["output"], m, rng), rep)[:n]
    o = np.maximum(1, np.minimum(o, max_seq_len - p))
    if (p + o > max_seq_len).any():
        raise ValueError("a prompt alone passes the engine's max_seq_len")
    return p, o


def draw_gaps(arrivals: dict, n: int, shape_seed: int,
              period: float) -> np.ndarray:
    """The cycle's `n` gaps between arrivals, scaled to add up to `period`
    seconds (a Poisson process, given its count, is just that)."""
    rng = _rng(shape_seed + 1)
    if arrivals["dist"] == "poisson":
        g = rng.exponential(1.0, n)
    elif arrivals["dist"] == "gamma":
        k = 1.0 / float(arrivals["cv"]) ** 2
        g = rng.gamma(k, 1.0 / k, n)
    else:
        raise ValueError(f"unknown arrival process {arrivals['dist']!r}")
    return g * (period / g.sum())


def make_requests(traffic: dict, seed: int, n: int, vocab: int,
                  max_seq_len: int, period: float = None):
    """`n` requests; request k fills slot (k + offset) of the cycle, the
    offset and the token ids from `seed`. Open loop (`arrivals`): the cycle
    has round(rate x period) slots and each request its due time, in
    seconds from the traffic's start. Closed loop: the cycle is `pool`
    slots and due is None; clients take requests in order and wrap."""
    rng = _rng(seed)
    rep = int(traffic.get("repeat", 1))
    arrivals = traffic.get("arrivals")
    cycle = int(traffic["pool"]) if arrivals is None else max(
        1, round(float(arrivals["rate"]) * period / rep)) * rep
    p, o = draw_sizes(traffic, cycle, max_seq_len)
    slots = (np.arange(n) + int(rng.integers(0, cycle // rep)) * rep) % cycle
    due = [None] * n
    if arrivals is not None:
        gaps = draw_gaps(arrivals, cycle, traffic["shape_seed"], period)
        due = np.cumsum(gaps[slots]).tolist()
    shared = traffic.get("shared_prefix")
    if shared:
        prefixes = rng.integers(0, vocab, (shared["tenants"],
                                           shared["tokens"]))
        w = 1.0 / np.arange(1, shared["tenants"] + 1) ** shared["zipf"]
        tenant = _rng(traffic["shape_seed"] + 2).choice(
            shared["tenants"], cycle, p=w / w.sum())
    out, last = [], None
    for i, j in enumerate(slots):
        if last is not None and j // rep == last[0] and j == last[1] + 1:
            ids = last[2]                         # the same prompt again
        else:
            ids = rng.integers(0, vocab, int(p[j])).tolist()
            if shared:
                k = min(shared["tokens"], len(ids) - 1)
                ids[:k] = prefixes[tenant[j]][:k].tolist()
        last = (j // rep, j, ids)
        out.append(Request(i, due[i], ids, int(o[j])))
    return out


def describe(values) -> dict:
    v = np.asarray(values, np.float64)
    return {"n": int(v.size), "mean": round(float(v.mean()), 1),
            "p50": float(np.percentile(v, 50)),
            "p95": float(np.percentile(v, 95)), "max": float(v.max())}


class ZipfBatches:
    """Train batches: token ids from a Zipf(a) law over the vocabulary
    (rank k has id k-1), a fresh batch every call, made on the host."""

    def __init__(self, vocab: int, a: float, batch: int, seq: int, seed: int):
        w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
        self.cdf = np.cumsum(w / w.sum())
        self.shape, self.vocab = (batch, seq), vocab
        self.rng = _rng(seed)

    def next(self) -> np.ndarray:
        ids = np.searchsorted(self.cdf, self.rng.random(self.shape))
        return np.minimum(ids, self.vocab - 1).astype(np.int32)
