"""Measured collective ladder on a real mesh (ISSUE 12).

`profiler/comm.py` accounts what a compiled program MOVES (payload
bytes per mesh axis, read back from the post-SPMD HLO); this step
measures what the interconnect DELIVERS: a psum / all-gather ladder
over the real mesh, timed with `kernels/timing.py::device_time` (the
device-side loop — a host-side loop times dispatch, not the op),
reported as achieved GB/s
against the ACCOUNTED bytes of the very program being timed. The two
legs keep each other honest: the accounting supplies the numerator,
the chip the denominator.

Per rung it prints
    COMM_CHIP <kind> elems=<n> accounted=<payload B> ms=<t> GB/s=<g>
where GB/s = payload / t (logical payload rate; ring all-reduce moves
~2(n-1)/n x payload per link — divide yourself for link-level numbers,
the same honest-reading rule as profiler/comm.py).

Gating (the chip_serving convention): accounting-vs-hand-computed
byte equality is HARD-asserted ON_TPU with >1 device; CPU runs (and a
single device, where a 1-sized axis legitimately emits no
collective) report-only, because the CPU path is covered by the pinned
tests in tests/test_profiler_comm.py and a single chip has nothing to
move. Not run on a chip since it was written.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

print("devices:", jax.devices())
ON_TPU = jax.default_backend() == "tpu"

# fp32 elements per rung; payloads 4 MB / 32 MB / 128 MB keep the
# largest all-gather result (x n devices) well under one chip's HBM
LADDER = (1 << 20, 8 << 20, 32 << 20)


def comm_mesh():
    """One flat axis over every visible device — the COMM ladder is an
    interconnect probe, not a parallelism layout."""
    devs = jax.devices()
    return Mesh(np.array(devs), ("x",)), len(devs)


def ladder_fns(mesh):
    """{kind: sharded collective fn} over the mesh's 'x' axis."""
    from jax import shard_map

    def mk(body):
        return jax.jit(shard_map(body, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x"), check_vma=False))

    return {
        "psum": mk(lambda a: jax.lax.psum(a, "x")),
        "all_gather": jax.jit(shard_map(
            lambda a: jax.lax.all_gather(a, "x", tiled=True), mesh=mesh,
            in_specs=P("x"), out_specs=P(None), check_vma=False)),
    }


def expected_payload(kind, n_elems, n_dev, itemsize=4):
    """Hand-computed payload bytes for one ladder rung — the number the
    IR walk must reproduce (profiler/comm.py payload rule: all-reduce
    at the operand entering it = the PER-SHARD block under shard_map
    (array/n), all-gather at the result it materializes = the full
    array (per-shard operand x group size))."""
    if n_dev <= 1:
        return 0          # a 1-sized axis emits no collective
    full = n_elems * itemsize
    return {"psum": full // n_dev, "all_gather": full}[kind]


def accounted_payload(fn, x, mesh):
    """The profiler.comm accounting of the compiled ladder program."""
    from paddle_tpu.profiler import comm as _comm
    rep = _comm.lowered_comm(fn.lower(x), mesh=mesh)
    return rep.payload_bytes, rep.to_dict()


def main():
    from paddle_tpu.kernels.timing import device_time
    mesh, n_dev = comm_mesh()
    fns = ladder_fns(mesh)
    if n_dev == 1:
        print("COMM_CHIP_SINGLE_DEVICE: one device — ladder times "
              "the identity program, accounting is honestly 0 bytes "
              "(report-only)")
    failures = []
    for kind, fn in fns.items():
        for n_elems in LADDER:
            x = jax.device_put(
                jnp.ones((n_elems,), jnp.float32),
                NamedSharding(mesh, P("x")))
            want = expected_payload(kind, n_elems, n_dev)
            try:
                got, rep = accounted_payload(fn, x, mesh)
            except Exception as e:               # noqa: BLE001
                got, rep = None, {"error": repr(e)}
            if got != want:
                msg = (f"COMM_ACCOUNT_MISMATCH {kind} elems={n_elems}: "
                       f"accounted={got} expected={want} ({rep})")
                if ON_TPU and n_dev > 1:
                    failures.append(msg)
                print(msg)
            dt = device_time(fn, x, iters=4)
            gbps = (want / dt / 1e9) if (dt == dt and dt > 0 and want) \
                else float("nan")
            print(f"COMM_CHIP {kind} elems={n_elems} accounted={want} "
                  f"ms={dt * 1e3:.3f} GB/s={gbps:.1f}")
    if failures:
        raise AssertionError("; ".join(failures))
    print("COMM_CHIP_OK")


if __name__ == "__main__":
    main()
