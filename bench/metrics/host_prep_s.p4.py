"""Host seconds per distributed count in which every chip waits on the
host's preparation: the program's ``tc.ingest``, ``tc.shard`` and
``tc.plan_layout`` spans on the benchmark's thread in the traced
window, where no device ran an op, over the counts made in it.
``None`` where the program records no ``tc.shard`` span."""

from bench import tracing

SPANS = ("tc.ingest", "tc.shard", "tc.plan_layout")


def read(ctx):
    t = ctx.trace
    spans = tracing.union_ns(
        (max(s, t.t0), min(e, t.t1)) for _, _, name, s, e in t.host
        if name in SPANS and s < t.t1 and e > t.t0)
    if not any(name == "tc.shard" for _, _, name, _, _ in t.host):
        return None
    ns = sum(max(0, min(e, ge) - max(s, gs))
             for s, e in spans for gs, ge in t.idle_gaps())
    return ns * 1e-9 / ctx.counters["counts"] if ns > 0 else None
