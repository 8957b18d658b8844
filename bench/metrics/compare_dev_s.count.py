"""Device seconds of the Pallas compare kernels per exact count: the
ops whose HLO name (``tracing.op_name``: the TPU event name without the
instruction text after it) starts with ``intersect``, in the traced
window, over the counts made in it.  The probe's dense gathers take
``probe_dev_s.count`` less this."""

from bench import tracing

OPS = r"^%?intersect"


def read(ctx):
    s = ctx.trace.device_s(OPS, line=tracing.OPS_LINE)
    return s / ctx.counters["counts"] if s > 0 else None
