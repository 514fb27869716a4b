"""The reduction from a profiler trace to busy time, kernel time, collective
time and idle gaps, on hand-made events and on a trace recorded on a TPU v5
lite (``data/tiny.xplane.pb``: the q8 kernels three times over, each pass
inside a ``chipbench:kernels`` span, then a 2 ms ``chipbench:idle`` span)."""
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.trace import Event

RECORDED = Path(__file__).parent / "data" / "tiny.xplane.pb"


def ev(name, start, dur):
    return Event(name, start, dur)


def test_busy_is_the_union_of_overlapping_ops():
    ops = [ev("a", 0.0, 2.0), ev("b", 1.0, 2.0), ev("c", 5.0, 1.0)]
    assert trace.union(ops) == [(0.0, 3.0), (5.0, 6.0)]
    assert trace.busy_s(ops, 0.0, 10.0) == pytest.approx(4.0)
    # clipped to the window
    assert trace.busy_s(ops, 2.0, 5.5) == pytest.approx(1.5)


def test_gaps_are_the_window_less_the_busy_intervals():
    ops = [ev("a", 1.0, 1.0), ev("b", 3.0, 1.0)]
    assert trace.gaps(ops, 0.0, 5.0) == [(0.0, 1.0), (2.0, 3.0), (4.0, 5.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_kernel_and_collective_time_sum_matching_events_in_the_window():
    ops = [ev("wsum_q8.3", 0.0, 1.0), ev("fusion.1", 1.0, 1.0),
           ev("wsum_q8.4", 2.0, 2.0), ev("all-gather.2", 4.0, 0.5),
           ev("all-reduce-start", 5.0, 0.25)]
    assert trace.kernel_s(ops, r"wsum", 0.0, 3.0) == pytest.approx(2.0)
    assert trace.collective_s(ops, 0.0, 10.0) == pytest.approx(0.75)


def test_idle_gaps_go_to_the_innermost_span_that_covers_them():
    spans = [ev("window", 0.0, 10.0), ev("train", 0.0, 6.0),
             ev("publish", 2.0, 1.0)]
    assert trace.host_label(spans, 2.1, 2.9) == "publish"
    assert trace.host_label(spans, 4.0, 5.0) == "train"
    assert trace.host_label(spans, 7.0, 8.0) == "other"
    tr = trace.Trace(device_ops={0: [ev("op", 0.0, 2.0), ev("op", 3.0, 3.0)]},
                     spans=spans)
    s = trace.device_summary(tr, [0], 0.0, 10.0)
    assert s["busy_s"] == pytest.approx(5.0)
    assert s["window_s"] == pytest.approx(10.0)
    assert s["breakdown"]["device_ops"] == [["op", pytest.approx(5.0)]]
    assert dict(s["breakdown"]["idle_gaps"]) == {
        "publish": pytest.approx(1.0), "other": pytest.approx(4.0)}


def test_a_recorded_chip_trace_reduces():
    tr = trace.load(str(RECORDED))
    assert list(tr.device_ops) == [0]
    ops = tr.device_ops[0]
    kernels = [e for e in ops if e.name.startswith("wsum_q8")]
    assert len(kernels) == 3                 # short op names, one per call
    names = {s.name for s in tr.spans}
    assert {"kernels", "idle"} <= names
    lo = min(s.start for s in tr.spans)
    hi = max(s.end for s in tr.spans)
    busy = trace.busy_s(ops, lo, hi)
    assert 0.0 < busy < hi - lo
    assert 0.0 < trace.kernel_s(ops, r"^wsum_q8(\.\d+)?$", lo, hi) <= busy
    idle = dict(trace.device_summary(tr, [0], lo, hi)["breakdown"]["idle_gaps"])
    # the 2 ms sleeps run with the device idle, inside the idle spans
    assert idle["idle"] >= 3 * 0.002
