"""Host seconds of ingest per exact count of the uniform graph:
``ingest_s.count`` for the cell that reports ``count_s.urand``."""

SPAN = "tc.ingest"


def read(ctx):
    t = ctx.trace
    ns = sum(min(e, t.t1) - max(s, t.t0) for _, _, name, s, e in t.host
             if name == SPAN and s < t.t1 and e > t.t0)
    return ns * 1e-9 / ctx.counters["counts"] if ns > 0 else None
