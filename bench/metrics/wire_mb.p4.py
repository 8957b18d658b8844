"""Wire bytes of one distributed count over all chips, in MB (1e6
bytes): the program's ``dist.wire_bytes`` counter (each count's
``CommTally`` over its phases, with its BFS sweeps) over
``dist.counts``, from ``repro.obs``.  ``None`` where the program keeps
no such counters."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    counts = c.get("dist.counts", 0)
    if not counts:
        return None
    return c.get("dist.wire_bytes", 0) / counts / 1e6
