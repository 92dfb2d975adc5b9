"""python benchmarks/rehearse_sizes.py [cell ...]

Compiles the steady programs of the benchmark's cells, at their real
sizes, for a DESCRIBED TPU v5e:2x2 (the TPU compiler is installed here;
no chip is attached) and prints each program's `memory_analysis()`. Run it
on the CPU before a chip call: what the chip's compiler refuses, or what
does not fit, costs no chip time here. Nothing runs, so it says nothing
about results or times, and it counts one program at a time, not what
else the process keeps on the device.

It builds the model on the CPU at the real widths (host memory: up to
12 GB for a serving cell), takes the programs the entry points would jit
(`to_static`'s jitted step after an abstract first step has created the
AdamW state; the engine's `_build_decode` / `_build_chunk`), and lowers
them for shapes placed on the described chip. It reaches into those two
private builders; it is a rehearsal tool, not part of the yardstick.
"""
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402

from benchmarks.harness import common, serve_loop, train_loop  # noqa: E402

GIB = 2.0 ** 30


def show(label, compiled):
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"[rehearse] {label}: arguments {m.argument_size_in_bytes / GIB:.2f}"
          f" + outputs {m.output_size_in_bytes / GIB:.2f} + temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} - aliased "
          f"{m.alias_size_in_bytes / GIB:.2f} = {peak / GIB:.2f} GiB a chip; "
          f"tpu_custom_call x{compiled.as_text().count('tpu_custom_call')}",
          flush=True)
    return peak


def placed(tree, to_chip):
    """ShapeDtypeStructs of a pytree of arrays, placed by `to_chip`."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=to_chip(a)), tree)


def rehearse_train(cell, cfg, topo):
    import paddle_tpu as paddle
    from paddle_tpu.jit import api
    mesh = cfg.get("mesh")
    built = train_loop.setup(cfg, cell, seed=0)
    step, t = built["step"], cell["traffic"]
    ids = paddle.Tensor(jnp.zeros((t["batch"], t["seq"]), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten(
        ((ids, ids), {}), is_leaf=api._is_tensor)
    step._sg_flags = [x.stop_gradient for x in leaves]
    entry = step._make_jitted(treedef, [api._TENSOR_SLOT] * 2, 2)
    state0 = step._bundle.collect()
    arrays = [ids._data, ids._data]
    # step 1, abstractly: AdamW creates its state; its shapes are step 2's
    _, state1 = jax.eval_shape(entry.jitted, state0, arrays)
    one = SingleDeviceSharding(topo.devices[0])
    if mesh:
        # the model was placed on a mesh of virtual CPU devices; lower it
        # for the same mesh over the described chips
        import numpy as np
        from paddle_tpu.distributed.fleet import fleet
        hcg = fleet.get_hybrid_communicate_group()
        chips = jax.sharding.Mesh(
            np.asarray(topo.devices, dtype=object).reshape(
                hcg.mesh.devices.shape), hcg.mesh.axis_names)
        hcg.mesh = chips
        data = NamedSharding(chips, built["sharding"].spec)

    def to_chip(a):
        if not mesh:
            return one
        if a.dtype == jnp.int32 and a.ndim == 2:
            return data
        sh = getattr(a, "sharding", None)
        spec = sh.spec if isinstance(sh, NamedSharding) \
            else jax.sharding.PartitionSpec()
        return NamedSharding(chips, spec)

    t0 = time.time()
    steady = placed(state1, to_chip)
    if mesh:
        # where step 1 leaves the state it created (the AdamW moments) is
        # the compiler's choice: compile step 1 and take its placement
        first = entry.jitted.lower(placed(state0, to_chip),
                                   placed(arrays, to_chip)).compile()
        steady = jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            state1, first.output_shardings[1])
    compiled = entry.jitted.lower(steady, placed(arrays, to_chip)).compile()
    print(f"[rehearse] {cell['name']}: steady step compiled in "
          f"{time.time() - t0:.0f}s")
    return show(f"{cell['name']} steady train step", compiled)


def rehearse_serve(cell, cfg, topo):
    import paddle_tpu as paddle
    _, _, eng = serve_loop.setup(cfg, cell, seed=0)
    eng._donate = (1, 2, 3, 4)           # as on the chip: caches donated
    one = SingleDeviceSharding(topo.devices[0])
    to_chip = lambda a: one
    base = placed((eng._state, eng._k_caches, eng._v_caches, eng._k_scales,
                   eng._v_scales), to_chip)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    key = placed(eng._null_key, to_chip)
    B, P, S = eng.batch_buckets[-1], eng.pages_buckets[-1], \
        eng.prefill_buckets[-1]
    i32 = jnp.int32
    peaks = []
    t0 = time.time()
    with paddle.no_grad():               # as the engine launches it
        dec = eng._build_decode(B, P).lower(
            *base, sds((B, 1), i32), sds((B, P), i32), sds((B,), i32),
            key).compile()
    print(f"[rehearse] decode B{B} P{P} compiled in {time.time() - t0:.0f}s")
    peaks.append(show(f"{cell['name']} decode B{B} x P{P}", dec))
    t0 = time.time()
    with paddle.no_grad():
        chunk = eng._build_chunk(S, P).lower(
            *base, sds((1, S), i32), sds((), i32), sds((), i32),
            sds((P,), i32), key).compile()
    print(f"[rehearse] chunk S{S} P{P} compiled in {time.time() - t0:.0f}s")
    peaks.append(show(f"{cell['name']} chunk S{S} x P{P}", chunk))
    return max(peaks)


def main(argv):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.kernels import flash_attention as fa
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    fa._INTERPRET_CACHE[0] = False       # the Mosaic lowering, not interpret
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    cells = argv or sorted(
        f[:-5] for f in os.listdir(os.path.join(common.BENCH_DIR,
                                                "workloads")))
    for name in cells:
        cell, cfg = common.load_cell(name)
        t0 = time.time()
        # by the configuration's entry point, whatever kind of driver
        # the cell names: each reaches into that entry's private builders
        entry = {"train": rehearse_train, "serve": rehearse_serve}
        if cfg.get("entry") not in entry:
            raise SystemExit(f"rehearse_sizes.py: configuration "
                             f"{cell['config']} has entry "
                             f"{cfg.get('entry')!r}; this tool rehearses "
                             f"{sorted(entry)}")
        peak = entry[cfg["entry"]](cell, cfg, topo)
        print(f"[rehearse] {name}: largest program {peak / GIB:.2f} GiB of "
              f"a v5e's 15.75 GiB ({time.time() - t0:.0f}s here)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
