"""A counter of the program: its change over the window (`since`:
"window", the default) or its total at the end of the run ("process").
`path` is dotted into obs["counters"][since]; a dict there is summed."""
from benchmarks.harness.common import resolve, total


def read(obs, args):
    v = resolve(obs["counters"][args.get("since", "window")], args["path"])
    if v is None:
        return None
    return total(v) * args.get("scale", 1.0)
