"""The trace reduction, on intervals and on a trace built by hand in the
shape of a TPU profile (host spans, ``XLA Ops`` and ``XLA Modules`` lines).
"""
import pytest

import trace_reduce


def test_merge_and_gaps():
    merged = trace_reduce.merge([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert merged == [(0, 4), (5, 6)]
    assert trace_reduce._gaps(merged, 0, 10) == [(4, 5), (6, 10)]
    assert trace_reduce.clip([(0, 4), (5, 6)], 3, 5.5) == [(3, 4), (5, 5.5)]


def test_span_at_picks_the_innermost():
    spans = [("profile", 0, 10), ("partition", 2, 4), ("evaluate", 12, 14)]
    assert trace_reduce._span_at(spans, 3) == "partition"
    assert trace_reduce._span_at(spans, 5) == "profile"
    assert trace_reduce._span_at(spans, 11) == "host"


class _Event:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Event(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


class _Trace:
    def __init__(self, planes):
        self.planes = [_Plane(*p) for p in planes]


def test_reduce_by_hand():
    """A window of 100 ns: ops cover [10, 30) and [25, 40) and [70, 80), one
    module runs [10, 40) and another [70, 80); the host is in ``partition``
    over [40, 70).  A second chip of the host, idle, is not the cell's."""
    pd = _Trace([
        ("/host:CPU", [("python3", [("window", 0, 100), ("profile", 5, 40),
                                    ("partition", 40, 70), ("other", 0, 5)])]),
        ("/device:TPU:0", [
            ("XLA Ops", [("fusion", 10, 30), ("sort", 25, 40), ("fusion", 70, 80),
                         ("fusion", 120, 130)]),
            ("XLA Modules", [("jit__lif_scan(7)", 10, 40), ("jit__run(3)", 70, 80)]),
        ]),
        ("/device:TPU:0 SparseCore", [("XLA Ops", [("x", 0, 100)])]),
        ("/device:TPU:1", [("XLA Ops", []), ("XLA Modules", [])]),
    ])
    red = trace_reduce.reduce(pd, ("profile", "partition"), [0])
    assert red["device_planes"] == 1
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)  # [10, 40) and [70, 80)
    assert red["modules"] == pytest.approx({"jit__lif_scan": 30e-9, "jit__run": 10e-9})
    assert red["ops"] == pytest.approx({"fusion": 30e-9, "sort": 15e-9})
    # Idle gaps by the span at their middle: [0, 10) in profile (at 5),
    # [40, 70) in partition, [80, 100) in no layer span.
    assert red["idle"] == pytest.approx({"profile": 10e-9, "partition": 30e-9,
                                         "host": 20e-9})


def test_reduce_averages_over_the_cells_chips():
    """On two chips the busy time is the mean of the planes: one busy for
    40 ns, one idle."""
    pd = _Trace([
        ("/host:CPU", [("python3", [("window", 0, 100)])]),
        ("/device:TPU:0", [("XLA Ops", [("fusion", 10, 50)])]),
        ("/device:TPU:1", [("XLA Ops", [])]),
        ("/device:TPU:2", [("XLA Ops", [("fusion", 0, 100)])]),
    ])
    red = trace_reduce.reduce(pd, (), [0, 1])
    assert red["device_planes"] == 2
    assert red["busy_s"] == pytest.approx(20e-9)
    assert red["idle"] == pytest.approx({"host": 80e-9})


@pytest.mark.parametrize("late_ops,complete", [
    ([("fusion", 60, 80)], True),   # both jobs busy 20 ns
    ([("fusion", 60, 65)], False),  # the second job's events lost
    ([], False),                    # the second job has none
])
def test_jobs_hold_the_same_device_time(late_ops, complete):
    """Jobs run from one outermost ``profile`` span to the next (a nested
    span of the same name opens no job); every job is the same, so one
    that holds less device time than the median job is a trace that lost
    events."""
    pd = _Trace([
        ("/host:CPU", [("python3", [("window", 0, 100), ("profile", 0, 20),
                                    ("profile", 1, 19), ("profile", 50, 70),
                                    ("profile", 120, 130)])]),
        ("/device:TPU:0", [("XLA Ops", [("fusion", 10, 30)] + late_ops)]),
    ])
    red = trace_reduce.reduce(pd, ("profile",), [0], job="profile")
    busy = 20e-9 if complete else sum(e - s for _, s, e in late_ops) * 1e-9
    assert red["job_busy_s"] == pytest.approx([20e-9, busy])
    assert trace_reduce.complete(red) is complete
    assert trace_reduce.reduce(pd, ("profile",), [0])["job_busy_s"] == []
