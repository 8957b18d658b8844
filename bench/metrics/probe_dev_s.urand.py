"""Device seconds of the probe per exact count of the uniform graph:
``probe_dev_s.count`` for the cell that reports ``count_s.urand``."""

PROGRAM = r"_run_batch"


def read(ctx):
    s = ctx.trace.device_s(PROGRAM)
    return s / ctx.counters["counts"] if s > 0 else None
