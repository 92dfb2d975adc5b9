"""kernels/timing.py — the dispatch-proof device timer.

These run on CPU, where dispatch overhead is small; they lock the CONTRACT (positive time for a resolvable op, NaN
sentinel instead of fabricated numbers, loop cap respected) rather than
TPU behavior, which tools/chip_*.py cover on hardware.
"""
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels.timing import device_time


def test_device_time_resolves_real_op():
    import math
    x = jnp.asarray(np.random.RandomState(0).randn(256, 256), jnp.float32)

    def op(a):
        return a @ a

    # a chunky matmul with a low floor MUST resolve to a positive time
    # — NaN here would mean the timer can't measure anything real
    dt = device_time(op, x, iters=4, signal_floor_s=0.002)
    assert not math.isnan(dt)
    assert 0 < dt < 1.0


def test_device_time_never_fabricates():
    # a 1-element op under an unreachable signal floor and a tiny cap:
    # the result must be either a genuine positive delta or the NaN
    # sentinel — never zero or negative (the pre-round-4 failure mode
    # was impossible >1.0-MFU numbers from fabricated near-zero times)
    x = jnp.ones((1,), jnp.float32)
    for _ in range(5):
        dt = device_time(lambda a: a + 1, x, iters=1, loop_cap=4,
                         signal_floor_s=10.0)
        assert dt != 0.0
        assert not (dt < 0)          # NaN or positive


def test_device_time_handles_int_only_args():
    # int args get a runtime-zero bump (cast of the traced epsilon), so
    # the body is NOT loop-invariant and int-only ops (gather,
    # embedding lookup) stay measurable
    ids = jnp.arange(1 << 16, dtype=jnp.int32)
    dt = device_time(lambda i: jnp.cumsum(i * 2), ids, iters=2,
                     signal_floor_s=0.002)
    assert dt != 0.0
    assert not (dt < 0)
