"""python benchmarks/control_gap_fresh.py --workload <serve cell> --seeds 1 2 3 ... [--seconds 20] [--controls 6]

`control_gap_routed.py` with a FRESH engine a seed. `control_gap.py` keeps
one engine alive over all its seeds and only draws the weights anew, which
saves a model and four programs a seed; but the engine then stands beside
the plain reference while it runs, and where the cell fills the chip (the
pools and weights of `serve-mixed-context` are 13 GiB of 15.75) a
13,056-position float32 forward does not fit next to it. Here every seed
goes through the command's own `measure` as a run of the cell does: the
engine and the model are dropped before the reference starts. Everything
else (the int8 control, the positions kept, `chiprun_out/
control_gap_routed.npz`) is `control_gap_routed.py`'s.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import control_gap, control_gap_routed  # noqa: E402


def main(argv=None) -> int:
    real = control_gap.one_engine
    control_gap.one_engine = lambda fam, serve_loop: (serve_loop.setup, {})
    try:
        return control_gap_routed.main(argv)
    finally:
        control_gap.one_engine = real


if __name__ == "__main__":
    sys.exit(main())
