"""Mean seconds per job in the NoC replay outside the device stepper: the
``evaluate`` span less its ``stepper`` child."""
import program_spans


def read(ctx: dict):
    return program_spans.host_seconds(ctx, "toolchain", "evaluate", "stepper")
