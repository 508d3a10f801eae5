"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>
.json``: the network, the platform and the toolchain's keywords) and a
traffic mix (``bench/traffic/<name>.json``: the stimulus).  One *job* is
what a user of the toolchain runs: ``profile_snn`` (LIF simulation of the
network under the stimulus) followed by ``run_toolchain`` (partition, map,
replay).  Every job of every run has the same input: the network, and the
stimulus and toolchain seed drawn from the traffic file's ``job_seed``.  A
mapping job's work and result depend on its seed, so a seed taken from
``--seed`` would make runs differ in work, not in noise; ``--seed`` is
printed and changes nothing.

Set-up builds the network, then runs one job on the run's own input, which
compiles every program the window uses; ``setup_s`` runs from process
start to its end.  The window then runs jobs back to back and closes at the
end of the first job that ends after ``--seconds``.  Its compile count is
printed before the result.  A configuration's ``toolchain.fault_schedule``
(cores and links that die at given steps) makes each job replay its trace
in segments and re-map after each lost core; the harness records what
each replay ran on, for the check.  Afterwards the last job is compared
with the plain reference (`check.py`), and each metric named in
``BENCHMARK.json`` for the cell is read by its reader,
``bench/metrics/<name>.py``: the end-to-end metrics with ``--trace 0``, the
per-layer ones with ``--trace 1``, where the window's first ``TRACE_JOBS``
jobs run under the JAX profiler with a host span around them, the profile
and each phase of the toolchain.

The last line of standard output is the result as JSON; the last lines of
standard error are the numbers compared, each beside its limit.  Without a
TPU, or with fewer chips than the cell asks for, the run prints no result
and exits non-zero.

A run that is still going when its budget (`budget_s`) has passed since
the process started prints every thread's stack and, last on standard
error, a line that says so, and exits with ``OVER_BUDGET`` and no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import check  # noqa: E402
import network  # noqa: E402
import trace_reduce  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# Fixed paths inside the checkout: the cache's path is part of its key.
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_out" / "trace"
LAYERS = ("profile", "partition", "mapping", "evaluate", "remap")
# Window jobs under the profiler, from the first: its trace buffers hold
# about 8 of the edge cell's jobs and drop the rest.
TRACE_JOBS = 5
# A check allows each run ``run_seconds`` + 60 s, and each cell 2 x 90 s more
# to compile, which its first run takes.
RUN_SLACK_S = 60
COMPILE_S = 180
OVER_BUDGET = 4


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) of a cell named in
    ``BENCHMARK.json``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's metrics of the kind the run reports."""
    table = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in table if cell in m.get("workloads", [cell])]


def read_metric(name: str, ctx: dict):
    """The value ``bench/metrics/<name>.py`` reads from ``ctx``, or None."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Meter:
    """Programs that XLA compiled, and those loaded from the persistent
    compile cache instead, from JAX's monitoring events."""

    def __init__(self, jax):
        self.programs = 0  # compiled or loaded from the cache
        self.cache_loads = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    def counts(self) -> tuple[int, int]:
        """(compiled, loaded from the cache) so far."""
        return self.programs - self.cache_loads, self.cache_loads


def _annotate_phases(jax) -> None:
    """Wrap the toolchain's phase functions, as ``run_toolchain`` resolves
    them, in host spans named after their layer; each re-map under a fault
    schedule is a ``remap`` span.  `recording` opens the ``evaluate`` span
    around each NoC replay."""
    from repro.core import pipeline

    for layer, attr in (("partition", "partition_phase"),
                        ("mapping", "mapping_phase"),
                        ("remap", "incremental_remap"),
                        ("remap", "scratch_remap")):
        fn = getattr(pipeline, attr)

        def wrapped(*args, _fn=fn, _layer=layer, **kwargs):
            with jax.profiler.TraceAnnotation(_layer):
                return _fn(*args, **kwargs)

        setattr(pipeline, attr, wrapped)


def make_topology(net: network.Network):
    from repro.snn.topology import SNNTopology

    return SNNTopology(
        name=net.name, layer_sizes=list(net.layer_sizes),
        syn_src=net.syn_src.astype("int32"), syn_dst=net.syn_dst.astype("int32"),
        weights=net.weights, input_size=net.input_size,
        input_rate=net.input_rate, input_amp=net.input_amp,
        target_spikes=net.target_spikes)


def toolchain_kwargs(toolchain: dict) -> dict:
    """``run_toolchain``'s keywords from a configuration's ``toolchain``.

    Its ``fault_schedule`` is a list of events: ``{"t": 150, "kind":
    "core", "ids": [12]}``, or ``{"t": 100, "kind": "link", "from": 16,
    "to": 17}`` for the link from one core to its neighbour.  It becomes
    the program's `FaultSchedule`, each link named by its id.
    """
    if "fault_schedule" not in toolchain:
        return toolchain
    import numpy as np

    from repro.nocsim.xy import link_count, link_endpoints
    from repro.runtime.faults import FaultEvent, FaultSchedule

    w, h = int(toolchain["mesh_w"]), int(toolchain["mesh_h"])
    tail, head = link_endpoints(np.arange(link_count(w, h)), w, h)
    events = []
    for ev in toolchain["fault_schedule"]:
        ids = ev.get("ids", ())
        if ev["kind"] == "link":
            ids = np.flatnonzero((tail == ev["from"]) & (head == ev["to"]))
            if ids.shape[0] != 1:
                raise ValueError(f"no mesh link from core {ev['from']} "
                                 f"to core {ev['to']}")
        events.append(FaultEvent(int(ev["t"]), ev["kind"],
                                 tuple(int(i) for i in ids)))
    return {**toolchain, "fault_schedule": FaultSchedule(events)}


@contextlib.contextmanager
def recording(span):
    """Wrap each NoC replay the toolchain makes in an ``evaluate`` span, and
    yield the list of what each replayed: how many records, its first and
    last step, and the partition and placement it replayed them on.  A
    fault-free job makes one replay; under a fault schedule the toolchain
    replays its trace in segments, each on the mapping then in force."""
    import numpy as np

    from repro.core import pipeline

    replay = pipeline.simulate_noc
    segments: list[dict] = []

    def recorded(trace_t, trace_src, trace_dst, part, placement, *args,
                 **kwargs):
        t = np.asarray(trace_t)
        if t.shape[0]:
            segments.append({"records": int(t.shape[0]),
                             "t_first": int(t.min()), "t_last": int(t.max()),
                             "part": np.array(part, dtype=np.int64),
                             "placement": np.array(placement, dtype=np.int64)})
        with span("evaluate"):
            return replay(trace_t, trace_src, trace_dst, part, placement,
                          *args, **kwargs)

    pipeline.simulate_noc = recorded
    try:
        yield segments
    finally:
        pipeline.simulate_noc = replay


def run_job(topo, params, config: dict, traffic: dict, seed: int, span):
    """One job: profile the network, then run the toolchain on the profile.
    Returns the profile, the toolchain's result, the job's record and its
    NoC replays as `recording` lists them."""
    from repro.core import run_toolchain
    from repro.snn import profile_snn

    t0 = time.perf_counter()
    with span("profile"):
        prof = profile_snn(topo, num_steps=int(traffic["num_steps"]),
                           seed=seed, params=params)
    t1 = time.perf_counter()
    with recording(span) as segments:
        res = run_toolchain(prof, seed=seed,
                            **toolchain_kwargs(config["toolchain"]))
    record = {"job_s": time.perf_counter() - t0, "profile_s": t1 - t0,
              **{f"{k}_s": v for k, v in res.phase_seconds.items()},
              "kept_steps": int(prof.num_steps),
              "transmissions": int(prof.num_spikes),
              "k": int(res.partition.k),
              "objective": check.reported_objective(
                  res, config["toolchain"]["objective"]),
              "avg_hop": float(res.mapping.avg_hop)}
    if res.degradation is not None:
        record["neurons_migrated"] = int(res.degradation["neurons_migrated"])
        record["spikes_dropped"] = int(res.noc.spikes_dropped)
    return prof, res, record, segments


def run_cell(config: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, metrics: list[dict], chips: int = 1,
             require_tpu: bool = True, compile_cache: bool = True,
             log=print) -> dict:
    """Set-up, window, check and metrics of one run; returns the result."""
    import jax

    devices = jax.devices()
    t_devices = time.perf_counter()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.snn import LIFParams

    cache = None
    if compile_cache:
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"[device] platform={devices[0].platform} kind={devices[0].device_kind} "
        f"count={len(devices)} compile_cache={cache}")
    meter = Meter(jax)
    net = network.build_network(config["network"])
    topo = make_topology(net)
    lif = config["lif"]
    params = LIFParams(decay=lif["decay"], threshold=lif["threshold"],
                       v_reset=lif["v_reset"], refractory=lif["refractory"])
    objective = config["toolchain"]["objective"]
    def no_span(_):
        return contextlib.nullcontext()

    t_network = time.perf_counter()
    prof, res, _, _ = run_job(topo, params, config, traffic, seed, no_span)
    setup_s = time.perf_counter() - _T0
    compiled0, loaded0 = meter.counts()
    # Where set-up goes: start to the devices (imports, the backend's
    # start), building the network, and the warm job.
    log(f"[setup] seconds={setup_s} devices_s={t_devices - _T0} "
        f"network_s={t_network - t_devices} "
        f"warm_job_s={_T0 + setup_s - t_network} compiles={compiled0} "
        f"cache_loads={loaded0} compile_and_load_s={meter.seconds}")
    del prof, res

    span = jax.profiler.TraceAnnotation if trace else no_span
    jobs, summaries = [], []
    with contextlib.ExitStack() as traced:
        if trace:
            _annotate_phases(jax)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # Host spans at level 1 only: the harness's own annotations and
            # JAX's dispatch, without every thread pool event or Python call.
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 1
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
            traced.callback(jax.profiler.stop_trace)
            traced.enter_context(span(trace_reduce.WINDOW))
        t_start = time.perf_counter()
        while True:
            prof, res, record, segments = run_job(topo, params, config,
                                                  traffic, seed, span)
            jobs.append(record)
            summaries.append(check.job_summary(prof, res, objective, segments))
            if len(jobs) == TRACE_JOBS:
                traced.close()  # the traced window's span, then the trace
            if time.perf_counter() - t_start >= seconds:
                break
            del prof, res
        window_s = time.perf_counter() - t_start
    compiled, loaded = meter.counts()
    log(f"[window] jobs={len(jobs)} seconds={window_s} "
        f"job_s={[j['job_s'] for j in jobs]} "
        f"compiles={compiled - compiled0} cache_loads={loaded - loaded0}")
    used = devices[:chips]
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in used) \
        if devices[0].platform == "tpu" else 0

    reduced = None
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(_xplane()), LAYERS,
                                      [d.id for d in used], job="profile")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"[trace] busy_s={reduced['busy_s']} window_s={reduced['window_s']} "
            f"job_busy_s={reduced['job_busy_s']} "
            f"modules={trace_reduce.top(reduced['modules'])}")
    gc.collect()
    checks, ref_stats = check.outcome(net, config, traffic, seed, prof, res,
                                      summaries, segments)
    ok = check.passed(checks)
    failed = sum(1 for s in summaries if not (ok and s == summaries[-1]))
    complete = reduced is not None and trace_reduce.complete(reduced)
    if reduced is not None and not complete:
        log("[trace] a traced job holds under 9/10 of the median job's device "
            "time: the trace lost events, so no device metric is read")
    ctx = {"setup_s": setup_s, "window_s": window_s, "jobs": jobs,
           "reference": ref_stats, "trace": reduced if complete else None,
           "config": config,
           "traffic": traffic, "neurons": net.num_neurons,
           "device_kind": devices[0].device_kind}
    values = {}
    for m in metrics:
        value = read_metric(m["name"], ctx)
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": ok, "attempted": len(jobs), "failed": failed,
              "metrics": values, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": trace_reduce.top(reduced["ops"]),
                               "idle_gaps": trace_reduce.top(reduced["idle"])}
    result["checks"] = checks
    return result


def budget_s(bench: dict, seconds: float) -> float:
    """Seconds a run may last from the start of its process: the cell's
    compile allowance, the window (``run_seconds``, or ``--seconds`` where
    that is longer) and 60 s more."""
    return COMPILE_S + max(float(bench["run_seconds"]), seconds) + RUN_SLACK_S


def arm_budget(budget: float):
    """Stop the process once ``budget`` seconds have passed since it
    started: every thread's stack, then a line that says why, on standard
    error, and the exit code ``OVER_BUDGET``.  Where the interpreter's lock
    is held so long that this cannot run, faulthandler's own watchdog dumps
    the stacks and exits 30 s later.  Returns the function that disarms
    both."""
    left = max(budget - (time.perf_counter() - _T0), 0.0)

    def stop():
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        print(f"bench/run.py: stopped {time.perf_counter() - _T0:.1f} s after "
              f"the start, over the run's budget of {budget:.0f} s",
              file=sys.stderr, flush=True)
        os._exit(OVER_BUDGET)

    timer = threading.Timer(left, stop)
    timer.daemon = True
    timer.start()
    faulthandler.dump_traceback_later(left + 30, exit=True)

    def disarm():
        timer.cancel()
        faulthandler.cancel_dump_traceback_later()

    return disarm


def _xplane() -> Path:
    found = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {TRACE_DIR}")
    return found[-1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    bench, cell, config, traffic = load_cell(args.workload)
    disarm = arm_budget(budget_s(bench, args.seconds))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    seed = int(traffic["job_seed"])
    print(f"[run] workload={args.workload} seed={args.seed} job_seed={seed}",
          flush=True)
    try:
        result = run_cell(config, traffic, seed, args.seconds,
                          bool(args.trace),
                          metrics_for(bench, cell["name"], bool(args.trace)),
                          chips=int(cell["chips"]),
                          log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    finally:
        disarm()
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
