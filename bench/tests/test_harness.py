"""A whole run of the harness at the tiny size on the CPU: set-up, window,
the comparison, and every metric reader."""
import json
import subprocess
import sys

import pytest

import check
import tiny
from conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cast,net", [("unicast", tiny.NETWORK),
                                     ("multicast", tiny.NETWORK),
                                     ("multicast", tiny.RANDOM)],
                         ids=["unicast", "multicast", "multicast-random"])
def test_untraced_run_is_correct(cast, net):
    res = tiny.run(cast, network=net)
    assert res["correct"], {k: c for k, c in res["checks"].items() if c["value"]}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("faults", [tiny.LINKS_ONLY, tiny.CORES],
                         ids=["links", "cores"])
def test_faulted_run_is_correct(faults):
    res = tiny.run(faults=faults)
    assert res["correct"], {k: c for k, c in res["checks"].items() if c["value"]}
    for name in check.FAULT_CHECKS + [f"noc_{f}" for f in check.NOC_FIELDS]:
        assert res["checks"][name]["value"] == 0, name
    assert set(res["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}


def test_traced_faulted_run_is_correct():
    """A traced run under the tiny core schedule stays correct, and the
    readers of the host phases find their numbers."""
    res = tiny.run(trace=True, faults=tiny.CORES)
    assert res["correct"], {k: c for k, c in res["checks"].items() if c["value"]}
    for name in ("profile_s", "partition_s", "mapping_s", "evaluate_s",
                 "interpart_share", "avg_hop", "profile_host_s"):
        assert res["metrics"][name]["value"] > 0, name


def test_trace_covers_the_first_jobs(monkeypatch):
    """The profiler traces the window's first ``TRACE_JOBS`` jobs only; the
    window runs on untraced."""
    import run

    monkeypatch.setattr(run, "TRACE_JOBS", 1)
    lines = []
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    res = run.run_cell(tiny.config(), tiny.traffic(), 2**33 + 7, 30.0, True,
                       bench["per_layer"], require_tpu=False,
                       compile_cache=False, log=lines.append)
    assert res["correct"] and res["attempted"] >= 2
    trace = next(line for line in lines if line.startswith("[trace]"))
    assert "job_busy_s=[0.0] " in trace


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


# A run whose job sleeps past a budget of 2 s.
OVER_BUDGET = f"""
import sys, time
sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent / "src")!r}]
import run

def job_that_sleeps(*args, **kwargs):
    time.sleep(120)

run.budget_s = lambda bench, seconds: 2.0
run.run_cell = job_that_sleeps
sys.argv = ["run.py", "--workload", {SPEC["workloads"][0]["name"]!r},
            "--seed", "1", "--seconds", "1"]
sys.exit(run.main())
"""


def test_a_run_over_its_budget_stops():
    import run

    proc = subprocess.run([sys.executable, "-c", OVER_BUDGET],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == run.OVER_BUDGET
    assert "Thread" in proc.stderr and "in job_that_sleeps" in proc.stderr
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith("bench/run.py: stopped") and "budget of 2 s" in last
    assert "{" not in proc.stdout


def test_the_budget_is_the_checks_allowance():
    """Compile allowance, window and 60 s; a longer window than
    ``run_seconds`` widens it."""
    import run

    assert run.budget_s(SPEC, 0.0) == 180 + SPEC["run_seconds"] + 60
    assert run.budget_s(SPEC, 500.0) == 180 + 500 + 60
