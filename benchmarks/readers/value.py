"""A number the driver worked out itself: `path` is dotted into obs
(`values.ttft_p95_ms`), times `scale`. None where the run has none."""
from benchmarks.harness.common import resolve


def read(obs, args):
    v = resolve(obs, args["path"])
    return None if v is None else v * args.get("scale", 1.0)
