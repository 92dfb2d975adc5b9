"""python benchmarks/control_gap_routed.py --workload <serve cell> --seeds 1 2 3 ... [--seconds 20] [--controls 6]

`control_gap.py` for a family whose reference leaves positions out of the
comparison (`position_logits_and_margins` and `ROUTE_TIE`, as family
`kimi_k2`: a position where a held expert stands at a routing tie is no
reading of precision). Everything is `control_gap.py`'s: one engine, the
benchmark's weights drawn anew a seed, one short window through the
command's own `measure`, the control from int8-a-column weights. Only the
two numbers differ: they are taken, for the program and for the control
alike, over the positions the family's `token_gaps` keeps, and the
reference alone decides which those are.

Beside the table it keeps every position's reading (`chiprun_out/
control_gap_routed.npz`: for each sampled request the distance from a
tie, the program's gap and each control's), so that the limit and
`ROUTE_TIE` can be held against other values without another chip run.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import control_gap  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "control_gap_routed.npz")
KEPT = []                  # every request's record, over all seeds


def control_of(fam, kinds, records):
    """`control_gap.control_of` with each request's distances from a
    routing tie recorded beside its gaps; the run's own comparison takes
    the positions `fam.token_gaps` would."""
    import numpy as np

    def token_gaps(w, pcfg, prompt, output, pad_to=None):
        ref, margin = fam.position_logits_and_margins(w, pcfg, prompt,
                                                      output, pad_to)
        at = np.arange(len(output))
        g = ref.max(-1) - ref[at, np.asarray(output)]
        rec = {"prompt": len(prompt), "output": len(output), "program": g,
               "margin": margin, "tie": fam.ROUTE_TIE}
        for kind in kinds:
            first = fam.position_logits(control_gap.Lowered(w, kind), pcfg,
                                        prompt, output, pad_to).argmax(-1)
            rec[kind] = ref.max(-1) - ref[at, first]
        records.append(rec)
        KEPT.append(rec)
        return g[margin >= fam.ROUTE_TIE], float(np.abs(ref).max())

    return token_gaps


def summary(records, who) -> dict:
    """The run's two numbers for `who` over the positions that stand
    clear of a tie, and how many of the sample's positions those are."""
    import numpy as np
    g = np.concatenate([r[who] for r in records])
    keep = np.concatenate([r["margin"] for r in records]) >= records[0]["tie"]
    return {"mean": float(g[keep].mean()), "widest": float(g[keep].max()),
            "tokens": int(keep.sum()), "of": int(g.size),
            "under_the_best": int((g[keep] > 0).sum()),
            "mean_over_all": float(g.mean()),
            "widest_over_all": float(g.max())}


def main(argv=None) -> int:
    import numpy as np
    real = control_gap.control_of, control_gap.summary
    control_gap.control_of, control_gap.summary = control_of, summary
    try:
        return control_gap.main(argv)
    finally:
        control_gap.control_of, control_gap.summary = real
        if KEPT:
            os.makedirs(os.path.dirname(OUT), exist_ok=True)
            np.savez(OUT, **{f"{n}.{k}": np.asarray(v)
                             for n, r in enumerate(KEPT)
                             for k, v in r.items()})


if __name__ == "__main__":
    sys.exit(main())
