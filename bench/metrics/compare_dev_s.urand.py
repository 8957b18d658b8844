"""Device seconds of the Pallas compare kernels per exact count of the
uniform graph: ``compare_dev_s.count`` for the cell that reports
``count_s.urand``."""

from bench import tracing

OPS = r"^%?intersect"


def read(ctx):
    s = ctx.trace.device_s(OPS, line=tracing.OPS_LINE)
    return s / ctx.counters["counts"] if s > 0 else None
