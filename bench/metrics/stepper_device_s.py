"""Device seconds per traced job of the JAX replay stepper (``jit__run``,
`nocsim/replay_jax.py`)."""

import trace_reduce

MODULE = "jit__run"


def read(ctx: dict):
    if ctx["trace"] is None:
        return None
    seconds = trace_reduce.module_seconds(ctx["trace"], MODULE)
    jobs = len(ctx["trace"]["job_busy_s"])
    return None if seconds is None or not jobs else seconds / jobs
