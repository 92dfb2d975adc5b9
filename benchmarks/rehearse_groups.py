"""python benchmarks/rehearse_groups.py <cell> [--experts-held N] [--num-pages N]

`rehearse_sizes.py` for a serve cell whose model keeps its cache in more
than one LAYER GROUP (`paddle_tpu/models/paged.py`): the engine's decode
and chunk programs then take a block table a group, stacked (G, B, P) and
(G, P), where `rehearse_sizes.py` writes (B, P) and (P,) out by hand. It
compiles the cell's largest decode and chunk programs for a DESCRIBED TPU
v5e:2x2 (nothing runs) and prints each one's `memory_analysis()` through
`rehearse_sizes.show`.

The two options cut what only the ARGUMENTS' size depends on, so that
the model and the pools fit this machine's memory beside the compiler:
`--experts-held` (the held experts' stack; the router keeps its width)
and `--num-pages` (the unbounded group's pool; the tables keep the
cell's width). The temporaries, the kernels and what Mosaic accepts are
the cell's; the full arguments follow from arithmetic (PERF.md section
4). A rehearsal tool, not part of the yardstick.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import rehearse_sizes as rs      # noqa: E402 (pins the CPU)

import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402
from jax.sharding import SingleDeviceSharding     # noqa: E402

from benchmarks.harness import common, serve_loop  # noqa: E402


def rehearse(cell, cfg, topo):
    import paddle_tpu as paddle
    _, _, eng = serve_loop.setup(cfg, cell, seed=0)
    eng._donate = (1, 2, 3, 4)           # as on the chip: caches donated
    one = SingleDeviceSharding(topo.devices[0])
    to_chip = lambda a: one
    base = rs.placed((eng._state, *eng._cache_lists()), to_chip)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    key = rs.placed(eng._null_key, to_chip)
    B, P, S = eng.batch_buckets[-1], eng.pages_buckets[-1], \
        eng.prefill_buckets[-1]
    groups = 1 + len(eng.allocator.windows)
    table = lambda *shape: sds(((groups,) if groups > 1 else ()) + shape,
                               jnp.int32)
    print(f"[rehearse] {groups} layer groups; pools of "
          f"{[eng.num_pages] + [g.pool.num_pages for g in eng.allocator.windows]}"
          f" pages over layers {eng._layer_groups}", flush=True)
    i32, peaks = jnp.int32, []
    for label, build, inputs in (
            (f"decode B{B} x P{P}", lambda: eng._build_decode(B, P),
             (sds((B, 1), i32), table(B, P), sds((B,), i32))),
            (f"chunk S{S} x P{P}", lambda: eng._build_chunk(S, P),
             (sds((1, S), i32), sds((), i32), sds((), i32), table(P)))):
        t0 = time.time()
        with paddle.no_grad():           # as the engine launches it
            compiled = build().lower(*base, *inputs, key).compile()
        print(f"[rehearse] {label} compiled in {time.time() - t0:.0f}s")
        peaks.append(rs.show(f"{cell['name']} {label}", compiled))
        out = os.path.join(common.REPO, ".bench_scratch")
        os.makedirs(out, exist_ok=True)      # for a look at copies
        with open(os.path.join(out, f"rehearse_{label.split()[0]}.txt"),
                  "w") as f:
            f.write(compiled.as_text())
    return max(peaks)


def main(argv):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.kernels import flash_attention as fa
    name, opts = argv[0], dict(zip(argv[1::2], argv[2::2]))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    fa._INTERPRET_CACHE[0] = False       # the Mosaic lowering, not interpret
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    cell, cfg = common.load_cell(name)
    if "--experts-held" in opts:
        cfg["experts_held"] = int(opts["--experts-held"])
    if "--num-pages" in opts:
        cfg["engine"] = dict(cfg["engine"],
                             num_pages=int(opts["--num-pages"]))
    t0 = time.time()
    peak = rehearse(cell, cfg, topo)
    print(f"[rehearse] {name}: largest program {peak / rs.GIB:.2f} GiB of a "
          f"v5e's 15.75 GiB ({time.time() - t0:.0f}s here)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
