"""Mean seconds per job in the partition phase (the program's phase clock)."""


def read(ctx: dict):
    return _mean(ctx, "partition_s")


def _mean(ctx: dict, key: str) -> float:
    return sum(j[key] for j in ctx["jobs"]) / len(ctx["jobs"])
