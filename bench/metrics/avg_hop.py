"""Mean over the window's jobs of the placement's reported average hop."""


def read(ctx: dict):
    return _mean(ctx, "avg_hop")


def _mean(ctx: dict, key: str) -> float:
    return sum(j[key] for j in ctx["jobs"]) / len(ctx["jobs"])
