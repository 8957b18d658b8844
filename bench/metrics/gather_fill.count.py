"""Share of the probe's dense-gather entries that are real neighbour
ids, in %: ``100 × probe.entries_real ÷ probe.entries_gathered`` from
the program's counter registry (``repro.obs``).  The counters cover
every exact count the process made, the warm-up's too; every count of
the cell is of the same graph and plan, so that is the window's ratio.
``None`` where the program keeps no such counters."""


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
