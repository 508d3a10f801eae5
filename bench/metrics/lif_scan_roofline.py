"""Percent of the LIF scan's roofline: the least time the chip could take
for the work the recurrence needs (`work.lif_least_seconds`, bandwidth
bound), over the device time of the ``jit__lif_scan`` module, both over
the traced jobs."""

import trace_reduce
import work

MODULE = "jit__lif_scan"


def read(ctx: dict):
    if ctx["trace"] is None:
        return None
    seconds = trace_reduce.module_seconds(ctx["trace"], MODULE)
    if not seconds:
        return None
    peak = work.peak_for(ctx["device_kind"])
    least = sum(work.lif_least_seconds(ctx["neurons"], j["kept_steps"],
                                       j["transmissions"], peak)
                for j in ctx["jobs"][:len(ctx["trace"]["job_busy_s"])])
    return 100.0 * least / seconds
