"""Device seconds of the plan pass (BFS, horizontal-edge compaction,
the paper's k) per exact count: the ``_plan_batch`` program's time in
the traced window over the counts made in it."""

PROGRAM = r"_plan_batch"


def read(ctx):
    s = ctx.trace.device_s(PROGRAM)
    return s / ctx.counters["counts"] if s > 0 else None
