"""Percent of the NoC-bound records that the unicast replay's screens pass
to the stepper, over the window's ``evaluate`` spans."""
import program_spans


def read(ctx: dict):
    spans = [s for s in program_spans.find(ctx, "toolchain", "evaluate")
             if "noc_records" in s.counters]
    records = sum(s.counters["noc_records"] for s in spans)
    if not records:
        return None
    return 100.0 * sum(s.counters.get("stepped", 0) for s in spans) / records
