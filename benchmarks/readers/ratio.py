"""num / (den x den_times) x scale; each a dotted path into obs, such as
`counters.window.serving.decode_tokens`, `values.steps` or
`cell.engine.max_batch_size`. None where a part is missing or den is 0."""
from benchmarks.harness.common import resolve, total


def read(obs, args):
    num = resolve(obs, args["num"])
    den = resolve(obs, args["den"])
    times = resolve(obs, args["den_times"]) if "den_times" in args else 1
    if num is None or den is None or times is None:
        return None
    den = total(den) * total(times)
    if not den:
        return None
    return total(num) / den * args.get("scale", 1.0)
