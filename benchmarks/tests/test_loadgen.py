"""The load generator: deterministic in --seed, the same work for every
seed, due times on the schedule."""
import numpy as np
import pytest

from benchmarks.harness import loadgen
from benchmarks.harness.serve_loop import Source

OPEN = {"shape_seed": 7, "arrivals": {"dist": "poisson", "rate": 5.0},
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                   "min": 32, "max": 2048},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 8, "max": 512}}


def make(traffic, seed, n, max_seq_len=2560, period=40.0):
    return loadgen.make_requests(traffic, seed, n, 32768, max_seq_len, period)


def test_same_seed_same_requests():
    big = 2 ** 31 + 11                      # more than 32 signed bits hold
    a, b = make(OPEN, big, 300), make(OPEN, big, 300)
    assert [(r.due, r.prompt, r.n_out) for r in a] \
        == [(r.due, r.prompt, r.n_out) for r in b]


def test_every_seed_offers_the_same_cycle_rotated():
    """One cycle = the window (40 s x 5/s = 200 arrivals): whatever the
    seed, any 200 consecutive requests are the same slots, rotated."""
    a, b = make(OPEN, 1, 500), make(OPEN, 2, 500)
    slot = lambda r0, r1: (round(r1.due - r0.due, 9), len(r1.prompt), r1.n_out)
    cyc = lambda rs, k: sorted(slot(x, y) for x, y in zip(rs[k:k + 200], rs[k + 1:k + 201]))
    assert cyc(a, 0) == cyc(b, 0) == cyc(a, 77)
    assert [len(r.prompt) for r in a[:200]] != [len(r.prompt) for r in b[:200]]
    assert a[0].prompt != b[0].prompt
    assert all(x.due < y.due for x, y in zip(a, a[1:]))
    assert a[200].due - a[0].due == pytest.approx(40.0)    # one cycle, exactly
    assert (len(a[0].prompt), a[0].n_out) == (len(a[200].prompt), a[200].n_out)


def test_lengths_are_clipped_and_trimmed_to_max_seq_len():
    reqs = make(OPEN, 3, 500)
    assert all(32 <= len(r.prompt) <= 2048 for r in reqs)
    assert all(1 <= r.n_out <= 512 for r in reqs)
    assert all(len(r.prompt) + r.n_out <= 2560 for r in reqs)
    tight = make(OPEN, 3, 500, max_seq_len=2100)
    assert max(len(r.prompt) + r.n_out for r in tight) <= 2100


def test_gamma_arrivals_keep_the_rate_and_burst():
    g = loadgen.draw_gaps({"dist": "gamma", "rate": 5.0, "cv": 2.5}, 4000, 1, 800.0)
    p = loadgen.draw_gaps({"dist": "poisson", "rate": 5.0}, 4000, 1, 800.0)
    assert g.sum() == pytest.approx(800.0) and p.sum() == pytest.approx(800.0)
    assert g.std() / g.mean() > 2.0 > 1.1 > p.std() / p.mean() > 0.9


def test_shared_prefix_and_repeat():
    t = dict(OPEN, shared_prefix={"tokens": 64, "tenants": 2, "zipf": 1.0},
             repeat=3)
    reqs = make(t, 5, 30)
    heads = {tuple(r.prompt[:31]) for r in reqs}
    assert len(heads) <= 2
    assert reqs[0].prompt == reqs[1].prompt == reqs[2].prompt != reqs[3].prompt


def test_open_source_follows_the_schedule_and_closed_source_the_clients():
    reqs = make(OPEN, 1, 50)
    src = Source(reqs)
    assert src.due(0.0) == []
    first = src.due(reqs[2].due)
    assert [r.idx for r in first] == [0, 1, 2]
    assert src.next_due() == reqs[3].due
    closed = dict(OPEN, pool=4)
    del closed["arrivals"]
    pool = make(closed, 1, 4)
    assert all(r.due is None for r in pool)
    src = Source(pool, clients=3)
    assert len(src.due(1.0)) == 3 and src.due(2.0) == []
    src.finished(pool[0])
    again = src.due(3.0)
    assert len(again) == 1
    src.finished(pool[1])
    wrapped = src.due(4.0)[0]                # the pool wraps: a fresh record
    assert wrapped.prompt == pool[0].prompt and wrapped is not pool[0]


def test_zipf_batches_are_seeded_and_skewed():
    a = loadgen.ZipfBatches(32768, 1.0, 2, 2048, 2 ** 31 + 5)
    b = loadgen.ZipfBatches(32768, 1.0, 2, 2048, 2 ** 31 + 5)
    x, y = a.next(), b.next()
    assert (x == y).all() and x.shape == (2, 2048) and x.dtype == np.int32
    assert (a.next() != x).any()             # a fresh batch every step
    assert 0 <= x.min() and x.max() < 32768
    assert (x < 10).mean() > 0.2             # the head of the law is hit often
