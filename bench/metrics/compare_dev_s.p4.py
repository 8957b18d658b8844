"""Device seconds of the Pallas compare kernels per distributed count
on the slowest chip: the ops whose HLO name starts with ``intersect``,
on each chip's plane in the traced window, the largest plane, over the
counts made in it."""

from bench import chips, tracing

OPS = r"^intersect"


def read(ctx):
    s = chips.slowest_s(ctx.trace, OPS, line=tracing.OPS_LINE)
    return s / ctx.counters["counts"] if s > 0 else None
