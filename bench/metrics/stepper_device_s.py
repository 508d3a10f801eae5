"""Device seconds per job of the JAX replay stepper (``jit__run``,
`nocsim/replay_jax.py`)."""

import trace_reduce

MODULE = "jit__run"


def read(ctx: dict):
    if ctx["trace"] is None:
        return None
    seconds = trace_reduce.module_seconds(ctx["trace"], MODULE)
    return None if seconds is None else seconds / len(ctx["jobs"])
