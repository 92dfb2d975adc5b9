"""A rate times the work one unit requires, as a share (%) of the chips'
peak: `rate` is a dotted path into obs (units a second, over all chips),
`work` a function of harness/work.py (operations or bytes a unit), `peak`
"flops" or "bytes"."""
from benchmarks.harness import work
from benchmarks.harness.common import resolve


def read(obs, args):
    rate = resolve(obs, args["rate"])
    if rate is None:
        return None
    need = getattr(work, args["work"])(obs["cfg"], obs["cell"], obs["values"])
    peak = work.chip_peaks(obs["device"]["kind"])[args["peak"]]
    return 100.0 * rate * need / (obs["chips"] * peak)
