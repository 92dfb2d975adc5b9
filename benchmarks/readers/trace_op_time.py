"""A kernel's share (%) of its roofline over the traced slice: the least
time the chip could take for the work (`work`, a work function found by
`lookup.work`, the configuration's family first, giving {"flops",
"bytes"}: a step's where `per` names the count of steps in the slice, else
the whole slice's) over the device time
of the ops whose name matches `pattern`. `bound`: "roofline" takes the
larger of operations over peak FLOP/s and bytes over peak bytes/s, "bytes"
or "flops" only that one. None without a trace, without a matching op,
without the work's inputs, or where the family has no such count."""
from benchmarks.harness import lookup, reduce_trace, work
from benchmarks.harness.common import resolve


def read(obs, args):
    if not obs.get("trace"):
        return None
    seconds = reduce_trace.op_time(obs["trace"], args["pattern"])
    count = lookup.work(obs["cfg"], args["work"])
    if not seconds or count is None:
        return None
    need = count(obs["cfg"], obs["cell"], obs["values"])
    if not need:
        return None
    times = resolve(obs, args["per"]) if "per" in args else 1
    if times is None:
        return None
    peaks = work.chip_peaks(obs["device"]["kind"])
    least = {"flops": need["flops"] / peaks["flops"],
             "bytes": need["bytes"] / peaks["bytes"]}
    bound = args.get("bound", "roofline")
    t = max(least.values()) if bound == "roofline" else least[bound]
    return 100.0 * t * times / (obs["chips"] * seconds)
