"""Percent of the window in which no operation ran on the device."""


def read(ctx: dict):
    trace = ctx["trace"]
    if trace is None or not trace["device_planes"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
