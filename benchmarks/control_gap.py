"""python benchmarks/control_gap.py --workload <serve cell> --seeds 1 2 3 ... [--seconds 20] [--controls 4]

The two READINGS a serving cell's limits are set from, taken once on the
chip when a limit is set or questioned (the benchmark's own runs never run
this): one process, one engine, and for each seed the benchmark's weights
drawn anew, one short window at the cell's own load through the command's
own `measure`, and over the served tokens of its sample the numbers the
run compares (the mean and the widest gap by which a served token's logit
lies under the plain reference's best). For the first `--controls` seeds
the same numbers are read for the CONTROL: the plain reference computed
from weights in the nearest precision below the one the configuration
states (int8 a column for bfloat16 or float16, bfloat16 for float32), at
each position of the same prompts and served tokens, for the token that
the lowered reference puts first. A limit holds where it lies
above every reading of the program and under the control's. The last line
is the table as JSON; PERF.md keeps the readings. Exits non-zero without a
TPU unless --cpu is given (the tests' toy cells).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def controls_for(dtype: str) -> tuple:
    return ("bfloat16",) if dtype == "float32" else ("int8",)


class Lowered:
    """Weights as the reference reads them, every matrix lowered on the
    way out (one at a time: no second copy of the model)."""

    def __init__(self, weights, kind: str):
        import jax
        import jax.numpy as jnp

        def int8(v):                   # symmetric, one scale a column
            f = v.astype(jnp.float32)
            scale = jnp.max(jnp.abs(f), axis=0, keepdims=True) / 127.0
            scale = jnp.where(scale > 0, scale, 1.0)
            return (jnp.round(f / scale) * scale).astype(v.dtype)

        # reduce_precision, not a cast there and back: the TPU compiler
        # takes such a pair of converts out (a first chip run read an fp8
        # control made that way at exactly 0)
        lower = {"int8": int8,
                 "bfloat16": lambda v: jax.lax.reduce_precision(v, 8, 7)}
        self.weights, self.kind = weights, kind
        self.lower = jax.jit(lower[kind])

    def __getitem__(self, k):
        v = self.weights[k]
        return self.lower(v) if v.ndim == 2 else v


def control_of(fam, kinds, records):
    """`fam.token_gaps`, recording beside each request's gaps those of
    each control in `kinds`."""
    import numpy as np

    def token_gaps(w, pcfg, prompt, output, pad_to=None):
        ref = fam.position_logits(w, pcfg, prompt, output, pad_to)
        at = np.arange(len(output))
        g = ref.max(-1) - ref[at, np.asarray(output)]
        rec = {"prompt": len(prompt), "output": len(output), "program": g}
        for kind in kinds:
            first = fam.position_logits(Lowered(w, kind), pcfg, prompt,
                                        output, pad_to).argmax(-1)
            rec[kind] = ref.max(-1) - ref[at, first]
        records.append(rec)
        return g, float(np.abs(ref).max())

    return token_gaps


def summary(records, who) -> dict:
    """The run's two numbers for `who`, over its sample's served tokens."""
    import numpy as np
    g = np.concatenate([r[who] for r in records])
    return {"mean": float(g.mean()), "widest": float(g.max()),
            "tokens": int(g.size), "under_the_best": int((g > 0).sum())}


def one_engine(fam, serve_loop):
    """`serve_loop.setup` that builds the engine once and, for every later
    seed, only draws the weights anew into it. It reaches into the
    engine's snapshot of the weights: a tool for setting limits, not part
    of the yardstick."""
    real, kept = serve_loop.setup, {}

    def setup(cfg, cell, seed):
        if not kept:
            kept["built"] = real(cfg, cell, seed)
            eng = kept["built"][2]
            kept["shutdown"], eng.shutdown = eng.shutdown, lambda: None
            return kept["built"]
        pcfg, model, eng = kept["built"]
        fam.load_weights(model, pcfg, cfg, seed)
        for k, t in model.state_dict().items():
            eng._state[k] = eng._place(t._data, getattr(t, "_spec", None))
        return kept["built"]

    return setup, kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--controls", type=int, default=4,
                    help="read the controls on the first N seeds")
    ap.add_argument("--cpu", default=None, metavar="DATA_DIR",
                    help="a toy cell of this data directory, on the CPU")
    args = ap.parse_args(argv)

    from benchmarks import run_cell
    from benchmarks.harness import common, lookup, serve_loop
    cell, cfg = common.load_cell(args.workload, args.cpu or common.BENCH_DIR)
    common.place_compile_cache()
    device = common.CPU_AS if args.cpu \
        else common.require_tpu(cell["chips"])
    fam = lookup.family(cfg)
    real_gaps, real_setup, table = fam.token_gaps, serve_loop.setup, []
    serve_loop.setup, kept = one_engine(fam, serve_loop)
    try:
        for n, seed in enumerate(args.seeds):
            kinds = controls_for(cfg["dtype"]) if n < args.controls else ()
            records = []
            fam.token_gaps = control_of(fam, kinds, records)
            line, _ = run_cell.measure(cell, cfg, device, seed=seed,
                                       seconds=args.seconds, trace=0,
                                       t_start=time.time())
            row = {"seed": seed, "correct": line["correct"],
                   "checks": line["checks"],
                   "requests": [(r["prompt"], r["output"]) for r in records],
                   **{who: summary(records, who)
                      for who in ("program",) + tuple(kinds)}}
            common.say(f"control: {row}")
            table.append(row)
    finally:
        fam.token_gaps, serve_loop.setup = real_gaps, real_setup
        if kept:
            kept["shutdown"]()
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
