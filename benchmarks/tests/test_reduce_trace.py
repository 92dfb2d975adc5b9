"""reduce_trace.py: stage one on a small trace recorded on the CPU
backend (host spans only: the CPU has no device plane), stage two on a
small hand-made table of rows with a chip trace's shape (one `XLA Ops`
line a device plane, instruction names, a `while` that encloses its body)."""
import os

import pytest

from benchmarks.harness import reduce_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_load_rows_finds_the_drivers_spans():
    rows = reduce_trace.load_rows(os.path.join(DATA, "cpu_slice.xplane.pb"))
    names = [r[0] for r in rows["host"]]
    assert names.count("bench.slice") == 1
    assert names.count("bench.train_step") == 3 == names.count("bench.sync")
    assert rows["devices"] == {} and reduce_trace.reduce(rows) == {}
    sl = next(r for r in rows["host"] if r[0] == "bench.slice")
    inner = [r for r in rows["host"] if r[0] != "bench.slice"]
    assert all(sl[1] <= r[1] and r[1] + r[2] <= sl[1] + sl[2] for r in inner)


ROWS = {
    "devices": {"/device:TPU:0": [
        ["fusion.1", 1.0, 0.4], ["custom-call.2", 1.4, 0.1],
        ["fusion.1", 2.0, 0.4], ["while.3", 2.5, 0.3], ["fusion.9", 2.6, 0.1],
        ["fusion.1", 9.0, 1.0]]},                     # outside the slice
    "host": [["bench.slice", 1.0, 2.0], ["bench.engine_step", 0.9, 1.0],
             ["bench.admit", 1.9, 0.05], ["bench.engine_step", 1.95, 1.0]],
}


def test_reduce_busy_idle_ops_and_gaps():
    r = reduce_trace.reduce(ROWS)
    assert r["window_s"] == pytest.approx(2.0) and r["n_devices"] == 1
    # busy: [1.0,1.5) + [2.0,2.4) + [2.5,2.8) (the while encloses fusion.9)
    assert r["busy_s"] == pytest.approx(1.2)
    assert r["ops"]["fusion.1"] == pytest.approx(0.8)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.8)]
    assert reduce_trace.op_time(r, r"^custom-call") == pytest.approx(0.1)
    gaps = dict(r["idle_gaps"])
    # [1.5,2.0): its middle 1.75 lies in the first engine_step only;
    # [2.4,2.5) and [2.8,3.0) lie in the second
    assert gaps["bench.engine_step"] == pytest.approx(0.8)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_without_the_slice_span_takes_the_device_extent():
    rows = {"devices": ROWS["devices"], "host": []}
    r = reduce_trace.reduce(rows)
    assert r["window_s"] == pytest.approx(9.0) and not r["slice_from_host_span"]
    assert dict(r["idle_gaps"]) == {"(no bench span)": pytest.approx(6.8)}
