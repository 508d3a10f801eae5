"""Percent of the device replay stepper's lane-cycles that do useful work:
grants (each moves a packet one hop) plus blocked requests, over loop
cycles times padded lanes, summed over the window's ``stepper`` spans."""
import program_spans


def read(ctx: dict):
    spans = program_spans.find(ctx, "toolchain", "stepper")
    if not spans:
        return None
    work = sum(s.counters["grants"] + s.counters["blocked"] for s in spans)
    lanes = sum(s.counters["cycles"] * s.counters["lanes"] for s in spans)
    return 100.0 * work / lanes
