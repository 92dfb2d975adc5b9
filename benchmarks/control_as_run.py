"""python benchmarks/control_as_run.py --workload <serve cell> --seed N [--seconds 20]

One run of the cell through the command's own `measure` in which the
tokens held against the plain reference are the CONTROL's, not the
program's: at each position of the sample's prompts and served outputs,
the token that the reference puts first when it is computed from weights
in the nearest precision below the configuration's (`control_gap.py`
`Lowered`: int8 a column for bfloat16). The run's own comparison, at the
limits the family has committed, then decides `correct`, and the control
has to come out NOT correct: the exit code is 0 where it does and 1 where
the limits let it through. `control_gap.py` and its siblings READ the
numbers a limit is set from; this SHOWS that the set limits refuse. The
last line is the run's result line. Exits non-zero without a TPU unless
--cpu is given (the tests' toy cells).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def controls_tokens(fam, kind):
    """`fam.token_gaps` with the control's first token in place of each
    served one; the reference, its positions and those it leaves out at a
    routing tie are the run's own."""
    import numpy as np
    from benchmarks.control_gap import Lowered

    def token_gaps(w, pcfg, prompt, output, pad_to=None):
        if hasattr(fam, "position_logits_and_margins"):
            ref, margin = fam.position_logits_and_margins(w, pcfg, prompt,
                                                          output, pad_to)
            keep = margin >= fam.ROUTE_TIE
        else:
            ref = fam.position_logits(w, pcfg, prompt, output, pad_to)
            keep = np.ones(len(output), bool)
        first = fam.position_logits(Lowered(w, kind), pcfg, prompt, output,
                                    pad_to).argmax(-1)
        gaps = ref.max(-1) - ref[np.arange(len(output)), first]
        return gaps[keep], float(np.abs(ref).max())

    return token_gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--cpu", default=None, metavar="DATA_DIR",
                    help="a toy cell of this data directory, on the CPU")
    args = ap.parse_args(argv)

    from benchmarks import control_gap, run_cell
    from benchmarks.harness import common, lookup
    cell, cfg = common.load_cell(args.workload, args.cpu or common.BENCH_DIR)
    common.place_compile_cache()
    device = common.CPU_AS if args.cpu \
        else common.require_tpu(cell["chips"])
    fam = lookup.family(cfg)
    kind, = control_gap.controls_for(cfg["dtype"])
    real = fam.token_gaps
    fam.token_gaps = controls_tokens(fam, kind)
    try:
        line, _ = run_cell.measure(cell, cfg, device, seed=args.seed,
                                   seconds=args.seconds, trace=0,
                                   t_start=time.time())
    finally:
        fam.token_gaps = real
    common.say(f"the {kind} control as a run: correct {line['correct']}, "
               f"checks {line['checks']}")
    print(json.dumps(line))
    return 1 if line["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
