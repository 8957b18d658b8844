"""Graphs per device batch in the window: the server's completed
answers over its flushes (``TriangleServer.summary()`` counters)."""


def read(ctx):
    return ctx.counters.get("lanes_per_flush") or None
