"""python benchmarks/sweep_rate.py --workload <open-loop cell> --rates 3 4 5 6 7 [--seconds 20]

Finds an open-loop cell's knee ONCE, on the chip: one process, one engine,
the cell's own lengths at each of the given arrival rates for --seconds
each (the engine is emptied in between). The knee is the highest rate at
which the waiting queue at the end of the window is no longer than at its
middle; the cell file then fixes `arrivals.rate` at about 0.8 x that, and
the benchmark never searches for a rate again. Exits non-zero without a
TPU. Prints one row a rate; the last line is the table as JSON.
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=26)
    args = ap.parse_args(argv)

    import numpy as np

    from benchmarks.harness import common, loadgen, serve_loop
    from benchmarks.harness.observe import Spans
    cell, cfg = common.load_cell(args.workload)
    common.place_compile_cache()
    common.require_tpu(cell["chips"])
    _, _, eng = serve_loop.setup(cfg, cell, args.seed)
    serve_loop.warm_programs(eng, cfg["vocab_size"],
                             np.random.default_rng(args.seed))
    programs = eng.num_compiled_programs
    table = []
    for rate in args.rates:
        traffic = copy.deepcopy(cell["traffic"])
        traffic["arrivals"]["rate"] = rate
        n = int(rate * args.seconds * 1.25) + 32
        reqs = loadgen.make_requests(traffic, args.seed, n,
                                     cfg["vocab_size"], eng.max_seq_len,
                                     period=args.seconds)
        tr = serve_loop.Traffic(eng, serve_loop.Source(reqs), Spans(), False)
        mid = None
        while (now := time.perf_counter()) - tr.t0 < args.seconds:
            if mid is None and now - tr.t0 >= args.seconds / 2:
                mid = (eng.scheduler.queue_depth, len(eng.scheduler.running))
            tr.tick()
        end = (eng.scheduler.queue_depth, len(eng.scheduler.running))
        we = tr.t0 + args.seconds
        tok = sum(1 for r in tr.sent for x in r.t_tokens if x < we)
        half = [r for r in tr.sent if r.due >= tr.t0 + args.seconds / 2]
        ttft = [r.t_tokens[0] - r.due for r in half if r.t_tokens]
        gaps = np.concatenate([np.diff(r.t_tokens) for r in tr.sent
                               if len(r.t_tokens) > 1] or [np.zeros(1)])
        row = {"rate": rate, "sent": len(tr.sent),
               "tokens_per_s": tok / args.seconds,
               "queue_mid": mid[0], "queue_end": end[0],
               "running_mid": mid[1], "running_end": end[1],
               "no_first_token_yet": len(half) - len(ttft),
               "ttft_p50_ms": common.percentile(ttft, 50) * 1e3 if ttft else None,
               "ttft_p95_ms": common.percentile(ttft, 95) * 1e3 if ttft else None,
               "itl_p50_ms": common.percentile(gaps, 50) * 1e3,
               "itl_p95_ms": common.percentile(gaps, 95) * 1e3,
               "steps": len(tr.steps),
               "sustained": end[0] <= mid[0]}
        used = tr.cancel_all()
        common.say(f"sweep: {row}; pages in use after emptying {used}")
        table.append(row)
    common.say(f"sweep: programs {programs} -> {eng.num_compiled_programs}")
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
