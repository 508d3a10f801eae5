"""Percent of the profiled transmissions that the partitioner's objective
counts between parts (spikes on cut synapses, or multicast volume)."""


def read(ctx: dict):
    jobs = ctx["jobs"]
    return 100.0 * sum(j["objective"] / j["transmissions"] for j in jobs) / len(jobs)
