"""95th percentile, in ms, of how late the load generator sent each
request after it was due: a starved generator reads as a fast server."""


def read(ctx):
    return ctx.counters.get("gen_late_p95_ms")
