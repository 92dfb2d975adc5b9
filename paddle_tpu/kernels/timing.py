"""Dispatch-proof on-device kernel timing.

A loop of independent host-side calls pays one dispatch (and, on a
remote access path, one host round trip) per call, so for a kernel of
microseconds it measures the host, not the op.

`device_time` instead runs the iterations ON DEVICE in one dispatch:
a `lax.fori_loop` whose loop-carried scalar feeds an
iteration-dependent, value-preserving epsilon into the first float arg
(defeats loop-invariant hoisting and any result caching), an
`optimization_barrier` forces each iteration's output to materialize
(keeps memory-bound ops honest), and a 1-element slice of the output
becomes the next carry (serializes iterations at ~zero extra HBM
traffic). The loop result is fetched to host (`float(...)`), which
synchronizes on any access path, and loops of N and 2N iterations are
differenced to cancel the dispatch + fetch overhead.

Used by bench_ops.py and kernels/autotune.py. No reference analog —
this is infrastructure for honest measurement of short kernels.
"""
from __future__ import annotations

import time

__all__ = ["device_time"]


def device_time(fn, *args, iters=10, signal_floor_s=0.02, loop_cap=512):
    """Seconds per call of fn(*args), timed device-side.

    Returns NaN when the op is too fast to resolve over the dispatch noise
    (non-positive 2N-N delta at the loop cap) — callers must not treat
    NaN as a time.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    def _bumpable(a):
        d = jnp.asarray(a).dtype
        return (jnp.issubdtype(d, jnp.floating)
                or jnp.issubdtype(d, jnp.integer))

    # prefer a float arg (epsilon is value-preserving but nonzero in
    # the IR); fall back to an int arg, where casting the traced tiny
    # float yields a runtime 0 that XLA cannot constant-fold — without
    # ANY bump the body is loop-invariant and hoistable
    bump_idx = next((j for j, a in enumerate(args)
                     if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)),
                    next((j for j, a in enumerate(args) if _bumpable(a)),
                         None))

    def make(n):
        @jax.jit
        def run(*a):
            def body(i, dep):
                aa = list(a)
                if bump_idx is not None:
                    eps = ((i.astype(jnp.float32) + dep) * 1e-38)
                    x = aa[bump_idx]
                    aa[bump_idx] = x + eps.astype(x.dtype)
                out = fn(*aa)
                tok = lax.optimization_barrier(out)
                leaf = jax.tree_util.tree_leaves(tok)[0]
                return jnp.ravel(leaf)[0].astype(jnp.float32)
            return lax.fori_loop(0, n, body, jnp.float32(0.0))
        return run

    def best_of(run, reps=3):
        float(run(*args))                    # compile / warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run(*args))                # host fetch = real sync
            best = min(best, time.perf_counter() - t0)
        return best

    n = max(1, min(iters, loop_cap // 2))   # first dispatch respects the cap
    while True:
        run_long, run_short = make(2 * n), make(n)
        delta = best_of(run_long) - best_of(run_short)
        at_cap = 2 * (4 * n) > loop_cap
        if delta > signal_floor_s or at_cap:
            if delta <= 0:
                # noise inversion at the cap: one retry (reusing the
                # compiled loops), then refuse to fabricate a time
                delta = best_of(run_long) - best_of(run_short)
                if delta <= 0:
                    return float("nan")
            return delta / n
        n *= 4
