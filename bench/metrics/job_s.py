"""Seconds per mapping job: the window's wall time over the jobs it completed."""


def read(ctx: dict):
    return ctx["window_s"] / len(ctx["jobs"])
