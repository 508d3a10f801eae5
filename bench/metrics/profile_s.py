"""Mean seconds per job in profile_snn, by the harness's clock."""


def read(ctx: dict):
    return _mean(ctx, "profile_s")


def _mean(ctx: dict, key: str) -> float:
    return sum(j[key] for j in ctx["jobs"]) / len(ctx["jobs"])
