"""Device seconds of the plan pass per exact count of the uniform
graph: ``plan_dev_s.count`` for the cell that reports ``count_s.urand``."""

PROGRAM = r"_plan_batch"


def read(ctx):
    s = ctx.trace.device_s(PROGRAM)
    return s / ctx.counters["counts"] if s > 0 else None
