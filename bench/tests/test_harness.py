"""A whole run of the harness at the tiny size on the CPU: set-up, window,
the comparison, and every metric reader."""
import json

import pytest

import tiny
from conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cast", ["unicast", "multicast"])
def test_untraced_run_is_correct(cast):
    res = tiny.run(cast)
    assert res["correct"], {k: c for k, c in res["checks"].items() if c["value"]}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reads_the_host_layers():
    res = tiny.run("unicast", trace=True)
    assert res["correct"]
    got = res["metrics"]
    # The CPU has no device plane: the readers of device metrics find
    # nothing and are left out, never reported as 0.
    for name in ("profile_s", "partition_s", "mapping_s", "evaluate_s",
                 "interpart_share", "avg_hop"):
        assert got[name]["value"] > 0, name
    for name in ("lif_scan_roofline", "stepper_device_s", "device_idle_share"):
        assert name not in got
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_refuses_to_run_off_the_chip():
    import run

    with pytest.raises(run.NoChip):
        run.run_cell(tiny.config(), tiny.traffic(), 0, 0.0, False, [],
                     log=lambda _: None)
