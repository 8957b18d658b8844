"""Share of the probe's dense-gather entries that are real neighbour
ids, in %, for the uniform graph: ``gather_fill.count`` for the cell
that reports ``count_s.urand``."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    gathered = c.get("probe.entries_gathered", 0)
    if not gathered:
        return None
    return 100.0 * c.get("probe.entries_real", 0) / gathered
