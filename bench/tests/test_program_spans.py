"""The readers of the program's own spans and counters, on traced runs of
the tiny cell: a unicast run on the device stepper reports all five; a
multicast run, which neither screens nor steps on the device, reports the
three that do not depend on the unicast replay."""
import pytest

import tiny

SPAN_METRICS = ("stepper_lane_occupancy", "stepped_share", "evaluate_host_s",
                "profile_host_s", "program_loads_per_job")
UNICAST_ONLY = ("stepper_lane_occupancy", "stepped_share")


@pytest.mark.parametrize("cast", ["unicast", "multicast"])
def test_traced_run_reads_the_programs_spans(cast):
    res = tiny.run(cast, trace=True)
    assert res["correct"]
    got = res["metrics"]
    expected = [m for m in SPAN_METRICS
                if cast == "unicast" or m not in UNICAST_ONLY]
    for name in expected:
        assert got[name]["value"] >= 0, name
    for name in SPAN_METRICS:
        assert (name in got) == (name in expected), name
    if cast == "unicast":
        assert 0 < got["stepper_lane_occupancy"]["value"] <= 100
        assert 0 < got["stepped_share"]["value"] <= 100
    assert got["evaluate_host_s"]["value"] > 0
    assert 0 < got["profile_host_s"]["value"] < got["profile_s"]["value"]


def test_readers_find_nothing_without_the_programs_spans():
    """A window whose jobs recorded no spans (a program without the
    recorder) leaves every one of them out."""
    import run

    ctx = {"jobs": [{}] * (10 ** 6)}
    for name in SPAN_METRICS:
        assert run.read_metric(name, ctx) is None, name
