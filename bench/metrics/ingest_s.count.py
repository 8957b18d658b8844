"""Host seconds of ingest per exact count: the program's ``tc.ingest``
spans (``from_edges``: normalize, sort and unique, CSR offsets, the
uploads) on the benchmark's thread in the traced window, over the
counts made in it.  The device waits on the host throughout such a
span.  ``None`` where the program records no such span."""

SPAN = "tc.ingest"


def read(ctx):
    t = ctx.trace
    ns = sum(min(e, t.t1) - max(s, t.t0) for _, _, name, s, e in t.host
             if name == SPAN and s < t.t1 and e > t.t0)
    return ns * 1e-9 / ctx.counters["counts"] if ns > 0 else None
