"""Seconds from process start to the end of the warm job."""


def read(ctx: dict):
    return ctx["setup_s"]
