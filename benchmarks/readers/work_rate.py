"""A rate times the work one unit requires, as a share (%) of the chips'
peak: `rate` is a dotted path into obs (units a second, over all chips),
`work` a work function found by `lookup.work` (the configuration's family
first: operations or bytes a unit), `peak` "flops" or "bytes". None where
the run has no such rate, or the family no such count."""
from benchmarks.harness import lookup, work
from benchmarks.harness.common import resolve


def read(obs, args):
    rate = resolve(obs, args["rate"])
    count = lookup.work(obs["cfg"], args["work"])
    if rate is None or count is None:
        return None
    need = count(obs["cfg"], obs["cell"], obs["values"])
    if need is None:
        return None
    peak = work.chip_peaks(obs["device"]["kind"])[args["peak"]]
    return 100.0 * rate * need / (obs["chips"] * peak)
