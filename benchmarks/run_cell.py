"""python benchmarks/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One cell of BENCHMARK.json, in this one process: builds the system under
test from `workloads/<cell>.json` and its `configs/<config>.json`, warms
the cell's own shapes (set-up), measures for --seconds, checks the outputs
against the plain reference outside the window, and prints the contract's
one JSON object as the last line of stdout. Exits non-zero and prints no
result when JAX finds no TPU or fewer chips than the cell asks, or when the
run fails. Starts no child process. See benchmarks/README.md.
"""
import time

T_START = time.time()      # set-up is counted from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def measure(cell, cfg, device, *, seed, seconds, trace, t_start=T_START):
    """Everything after the look for a chip: finds the cell's driver by
    name (`drivers/<kind>.py` beside the cell's files), runs it, and
    returns (the result line as a dict, or None where a traced run holds
    no device operation; obs)."""
    from benchmarks.harness import common, lookup, observe
    phases = common.Phases(t_start)
    phases.mark("import")
    cache = common.CacheCounter()
    spans = observe.Spans()
    tracer = None
    if trace:
        tracer = observe.Tracer(os.path.join(
            common.REPO, ".bench_trace", cell["name"]), spans)
    e2e, obs, correct, attempted, failed = lookup.driver(cell).run(
        cfg, cell, seed=seed, seconds=seconds, cache=cache, phases=phases,
        tracer=tracer, spans=spans)
    setup_s = phases.total()
    obs.update(cfg=cfg, cell=cell, device=device, chips=cell["chips"],
               trace=None, phases=dict(phases.parts))
    peak = obs["memory_peak_bytes"]     # read at the window's close
    common.say(f"set-up {setup_s:.1f}s of "
               f"{ {k: round(v, 1) for k, v in phases.parts.items()} } "
               f"(all but {list(phases.NOT_SETUP)}); "
               f"persistent compile cache hits {cache.hits} misses "
               f"{cache.misses}; peak HBM {peak / 2**30:.2f} GiB")
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "device": dev}
    if trace:
        reduced, rows = tracer.reduce()
        common.say(f"trace: lines {rows and rows['lines']}")
        if not reduced or reduced["busy_s"] <= 0:
            print("run_cell.py: the trace holds no device operation",
                  file=sys.stderr)
            return None, obs
        obs["trace"] = reduced
        common.say(f"trace: slice {reduced['window_s']:.3f}s (from the host "
                   f"span: {reduced['slice_from_host_span']}), busy "
                   f"{reduced['busy_s']:.3f}s a chip over "
                   f"{reduced['n_devices']} device plane(s)")
        top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:30]
        common.say(f"trace: device ops by time (s a chip) "
                   f"{[(k, round(v, 4)) for k, v in top]}")
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["metrics"] = observe.read_metrics(obs, cell["bench_dir"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    else:
        # of what the driver measured, the metrics BENCHMARK.json gives
        # this cell (all of them for a cell it does not list yet)
        listed = [m for m in common.load_json(os.path.join(
            common.REPO, "BENCHMARK.json"))["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])]
        keep = {m["name"] for m in listed} if len(listed) > 1 else set(e2e)
        line["metrics"] = {k: {"value": float(v), "unit": u}
                           for k, (v, u) in e2e.items() if k in keep}
        line["metrics"]["setup_s"] = {"value": float(setup_s), "unit": "s"}
    # each number compared beside its limit: last in the line, and the
    # last lines on standard error
    line["checks"] = {k: {"value": float(v) if v == v else None,  # no NaN
                          "limit": float(lim)}
                      for k, (v, lim) in obs["checks"].items()}
    return line, obs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import common
    cell, cfg = common.load_cell(args.workload)
    cache_dir = common.place_compile_cache()
    device = common.require_tpu(cell["chips"])
    import paddle_tpu  # noqa: F401 — the system under test
    from paddle_tpu.kernels.autotune import autotune_enabled
    if autotune_enabled():
        print("run_cell.py: kernel autotune must be off (nothing outside "
              "the checkout may shape a kernel)", file=sys.stderr)
        return 1
    common.say(f"cell {cell['name']} ({cell['driver']}) config "
               f"{cell['config']} seed {args.seed} seconds {args.seconds} "
               f"trace {args.trace}; device {device}; compile cache "
               f"{cache_dir}")
    line, _ = measure(cell, cfg, device, seed=args.seed,
                      seconds=args.seconds, trace=args.trace)
    common.say(f"compile cache: {common.cache_state(cache_dir)}")
    if line is None:
        return 1
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
