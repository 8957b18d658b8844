"""Device seconds of the probe (dense gather and the Pallas intersect
kernels inside ``run_plan``) per exact count: the ``_run_batch``
program's time in the traced window over the counts made in it."""

PROGRAM = r"_run_batch"


def read(ctx):
    s = ctx.trace.device_s(PROGRAM)
    return s / ctx.counters["counts"] if s > 0 else None
