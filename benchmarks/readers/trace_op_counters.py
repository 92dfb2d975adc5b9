"""`trace_op_time` for a work count that reads the PROGRAM's counters: a
kernel's share (%) of its roofline over the traced slice, where the work
the slice required is counted by the program itself (how many rows reached
which expert) and not known from the traffic alone. The program writes
each launch's counts into the profiler's trace as the metadata of a
`serving.model_counters` span (`serving/engine.py` `_count_model`, beside
the launch's device ops and on their clock); this reader sums those that
start inside the driver's `bench.slice` span and hands them to the work
function as `values["slice_counters"]`: the work and the ops' time are of
the SAME interval, whatever the slice's mix of chunk and decode launches.
Everything else, and every reason to return None, is `trace_op_time`'s;
None too where the trace holds no such span (a program older than the
counters, or a family that counts nothing)."""
import os

from benchmarks.harness import reduce_trace
from benchmarks.harness.common import REPO
from benchmarks.readers import trace_op_time

SPAN = "serving.model_counters"
_SUMS: dict = {}               # xplane path -> the slice's sums, once a run


def _metadata(event) -> dict:
    """An annotation's keyword metadata: the trace gives it as the event's
    stats, or inside its name (`name#k=v,k=v#`)."""
    got = {}
    name, _, inside = event.name.partition("#")
    for pair in inside.rstrip("#").split(","):
        k, _, v = pair.partition("=")
        if v:
            got[k] = v
    for k, v in event.stats:
        got[k] = v
    out = {}
    for k, v in got.items():
        try:
            out[k] = float(v)
        except (TypeError, ValueError):
            pass
    return out


def sum_in_slice(path: str):
    """{counter: its sum over the `SPAN` events that start inside the
    slice}; None without the slice's span or without any such event."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    events = [e for plane in data.planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for e in line.events
              if e.name.startswith((SPAN, reduce_trace.SLICE_SPAN))]
    sl = [e for e in events if e.name.startswith(reduce_trace.SLICE_SPAN)]
    if not sl:
        return None
    lo, hi = sl[0].start_ns, sl[0].start_ns + sl[0].duration_ns
    sums, seen = {}, False
    for e in events:
        if e.name.startswith(SPAN) and lo <= e.start_ns < hi:
            seen = True
            for k, v in _metadata(e).items():
                sums[k] = sums.get(k, 0.0) + v
    return sums if seen else None


def slice_counters(obs):
    path = reduce_trace.find_xplane(os.path.join(
        REPO, ".bench_trace", obs["cell"]["name"], "slice"))
    if path is None:
        return None
    if path not in _SUMS:
        _SUMS[path] = sum_in_slice(path)
    return _SUMS[path]


def read(obs, args):
    if not obs.get("trace"):
        return None
    counters = slice_counters(obs)
    if not counters:
        return None
    values = dict(obs.get("values") or {}, slice_counters=counters)
    return trace_op_time.read(dict(obs, values=values), args)
