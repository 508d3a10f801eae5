"""Average NoC packet latency in cycles of the scored placement, as the
plain reference's replay of the last job computes it."""


def read(ctx: dict):
    ref = ctx["reference"]
    return None if ref is None else ref["avg_latency"]
