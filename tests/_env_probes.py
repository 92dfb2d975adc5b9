"""Environment capability probes for the known-env-sensitive tests.

Nine distributed/pipeline tests (CHANGES.md PR 2) fail on containers
whose jax CPU backend lacks specific capabilities — a memorized failure
set that made tier-1 output noise instead of signal. Each test is now
gated on the PROBE that reproduces its failure class, so it SKIPS with
an explicit reason where the capability is absent and RUNS everywhere
else (the probes pass on a capable jax build; nothing is permanently
disabled).

Probes are cached per test session (`functools.lru_cache`) and return
`(ok, reason)`; use them via the `skip_unless(probe)` marker helper.

Failure classes in this container (jax 0.4.37 CPU):

* multiprocess_collectives — two `jax.distributed.initialize`'d CPU
  processes running one jitted cross-process reduction die with
  "Multiprocess computations aren't implemented on the CPU backend"
  (gates the cross-process dp2/tp4_dp2/ep_moe convergence tests and the
  fake-multinode launch test).
* partial_manual_shard_map — a shard_map manual on ONE axis of a
  multi-axis mesh (the pipeline's partial-manual lowering) hits
  "UNIMPLEMENTED: PartitionId instruction is not supported for SPMD
  partitioning" (gates the llama_pipe tests and pp_tp_zero).
* host_offload_remat — the offload-dots-to-host checkpoint policy
  outside jit raises "TransferToMemoryKind ... only be used inside
  jax.jit" on this jax version (gates recompute_offload).
* gspmd_tp_mesh — whether the backend forms the hybrid mesh with
  model degree > 1 and runs the TP paged-attention lowering on it (a
  shard_map manual over EVERY axis — not the partial-manual map the
  pipeline needs; the two capabilities differ here). Gates the TP
  serving tests, ISSUE 8.
* banked_average_bitwise — whether this XLA CPU build rounds
  `((g+g+g)/3)*lr` bitwise-equal to `g*lr`; where it does not, the
  gradient-merge k-step-vs-single-step equality check differs by ~1 ulp
  which its rtol-only tolerance cannot absorb on near-zero weights
  (gates gradient_merge).
"""
from __future__ import annotations

import functools
import os
import socket
import subprocess
import sys
import textwrap

import pytest

_PROBE_TIMEOUT_S = 120


def skip_unless(probe):
    """Skip the test when the cached probe reports the capability
    absent. Lazy: the probe runs at test CALL time, not at decorator
    evaluation — collecting (or deselecting) a gated module must not
    pay for subprocess probes."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            ok, reason = probe()
            if not ok:
                pytest.skip(f"env capability absent: {reason}")
            return fn(*args, **kwargs)
        return wrapper
    return deco


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@functools.lru_cache(maxsize=None)
def multiprocess_collectives():
    """Can two jax.distributed CPU processes run one jitted
    cross-process reduction?"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    payload = textwrap.dedent("""
        import os, sys
        rank, port = int(sys.argv[1]), sys.argv[2]
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.distributed.initialize(f"127.0.0.1:{port}",
                                   num_processes=2, process_id=rank)
        import numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()), ("d",))
        x = jax.make_array_from_callback(
            (2,), NamedSharding(mesh, P("d")),
            lambda idx: np.ones((1,), np.float32))
        y = jax.jit(lambda a: jnp.sum(a),
                    out_shardings=NamedSharding(mesh, P()))(x)
        jax.block_until_ready(y)
        print("MP_PROBE_OK")
    """)
    path = os.path.join(repo, "tests", "_mp_probe_payload.py")
    try:
        with open(path, "w") as f:
            f.write(payload)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        port = str(_free_port())
        procs = [subprocess.Popen(
            [sys.executable, path, str(r), port], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            for r in range(2)]
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=_PROBE_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                return False, "multiprocess CPU collective probe timed out"
            outs.append((p.returncode, out))
        if all(rc == 0 and "MP_PROBE_OK" in out for rc, out in outs):
            return True, "multiprocess CPU collectives work"
        tail = next((o for rc, o in outs if rc != 0), outs[0][1])
        tail = tail.strip().splitlines()[-1] if tail.strip() else "no output"
        return False, f"jax CPU backend refuses multiprocess collectives " \
                      f"({tail[:160]})"
    finally:
        try:
            os.remove(path)
        except OSError:
            pass


@functools.lru_cache(maxsize=None)
def partial_manual_shard_map():
    """Can a shard_map manual on one axis of a multi-axis mesh (the
    pipeline's partial-manual lowering) compile on this backend?"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    devs = jax.devices()
    if len(devs) < 8:
        return False, f"needs the 8-device test mesh, have {len(devs)}"
    mesh = Mesh(np.array(devs[:8]).reshape(2, 4), ("pipe", "rest"))
    f = shard_map(
        lambda: jax.lax.axis_index("pipe") * jnp.ones((1,), jnp.float32),
        mesh=mesh, in_specs=(), out_specs=P("pipe"),
        axis_names={"pipe"}, check_vma=False)
    try:
        jax.block_until_ready(jax.jit(f)())
        return True, "partial-manual shard_map lowers"
    except Exception as e:                                 # noqa: BLE001
        return False, (f"partial-manual shard_map fails on this backend "
                       f"({str(e).splitlines()[0][:160]})")


@functools.lru_cache(maxsize=None)
def host_offload_remat():
    """Does the offload-dots-to-host remat policy work outside jit on
    this jax version?"""
    import jax
    import jax.numpy as jnp
    try:
        pol = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
        g = jax.grad(lambda x: jnp.sum(
            jax.checkpoint(lambda a: jnp.tanh(a @ a), policy=pol)(x)))
        jax.block_until_ready(g(jnp.ones((4, 4), jnp.float32)))
        return True, "host-offload remat policy works eagerly"
    except Exception as e:                                 # noqa: BLE001
        return False, (f"host-offload remat unusable outside jit on this "
                       f"jax ({str(e).splitlines()[0][:160]})")


@functools.lru_cache(maxsize=None)
def gspmd_tp_mesh():
    """Can this backend form the hybrid mesh with model degree > 1 and
    run the TP paged-attention lowering on it — a shard_map manual over
    every axis around the (interpret-mode) kernel, the one lowering
    kernels.paged_attention.paged_attention_decode_tp has on every
    backend? This is what TP serving (ISSUE 8) asks of the backend
    beyond GSPMD-sharded matmuls. Single-process, in-process probe: no
    subprocess needed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < 2:
        return False, (f"model-axis sharding needs >= 2 devices, "
                       f"have {len(devs)}")
    try:
        from paddle_tpu.kernels.paged_attention import \
            paged_attention_decode_tp
        mesh = Mesh(np.asarray(devs[:2], dtype=object).reshape(
            1, 1, 1, 1, 2), ("data", "pipe", "sharding", "sep", "model"))
        B, KVH, H, D, page, npages = 1, 2, 4, 64, 8, 4
        kc = jnp.zeros((npages, KVH, page, D), jnp.float32)
        q = jnp.ones((B, H, D), jnp.float32)
        bt = jnp.zeros((B, 2), jnp.int32)
        sl = jnp.full((B,), 4, jnp.int32)
        jax.block_until_ready(jax.jit(
            lambda q, kc, vc: paged_attention_decode_tp(
                q, kc, vc, bt, sl, mesh))(q, kc, kc))
        return True, "model-axis mesh runs the per-shard paged kernel"
    except Exception as e:                                 # noqa: BLE001
        return False, (f"TP mesh unusable on this backend "
                       f"({str(e).splitlines()[0][:160]})")


@functools.lru_cache(maxsize=None)
def subprocess_workers():
    """Can this environment spawn python subprocesses and bind the
    native TCPStore loopback mailbox — the substrate of the
    cross-process fleet (ISSUE 14)? Light probe: a trivial child
    process + one store set/get; the heavyweight jax-importing worker
    is only ever spawned by tests this gates."""
    try:
        from paddle_tpu._native import TCPStore
    except Exception as e:                                 # noqa: BLE001
        return False, f"native TCPStore unavailable ({str(e)[:120]})"
    try:
        store = TCPStore("127.0.0.1", _free_port(), is_master=True,
                         timeout_ms=5000)
        store.set("probe", b"x")
        if bytes(store.get("probe")) != b"x":
            return False, "TCPStore loopback roundtrip corrupted"
        del store
    except Exception as e:                                 # noqa: BLE001
        return False, f"TCPStore loopback failed ({str(e)[:120]})"
    env = dict(os.environ)
    try:
        out = subprocess.run(
            [sys.executable, "-c", "print('SPAWN_OK')"], env=env,
            capture_output=True, timeout=_PROBE_TIMEOUT_S, text=True)
    except Exception as e:                                 # noqa: BLE001
        return False, f"python subprocess spawn failed ({e})"
    if out.returncode != 0 or "SPAWN_OK" not in out.stdout:
        return False, "python subprocess spawn failed"
    return True, "subprocess + TCPStore loopback work"


@functools.lru_cache(maxsize=None)
def banked_average_bitwise():
    """Does this XLA CPU build round a k-step banked-average update
    bitwise-identically to the direct update? (The gradient-merge test
    asserts k banked steps == one step under rtol only; a 1-ulp
    difference on a near-zero weight breaks it.)"""
    import jax.numpy as jnp
    import numpy as np
    g = jnp.asarray(np.random.RandomState(0).randn(256).astype(np.float32))
    merged = ((g + g + g) / 3.0) * 0.1
    direct = g * 0.1
    if bool(jnp.all(merged == direct)):
        return True, "banked-average update rounds bitwise-equal"
    return False, ("XLA CPU rounds ((g+g+g)/3)*lr != g*lr by ~1 ulp; the "
                   "gradient-merge equality check cannot hold here")
