"""Share of the rows Algorithm 2's devices probe that are real
horizontal edges, in %: ``100 × dist.rows_real ÷ dist.rows_planned``
from the program's counter registry (``repro.obs``).  The counters
cover every distributed count of the process, the warm-up's too; every
count of the cell is of the same graph and plan, so that is the
window's ratio.  ``None`` where the program keeps no such counters."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    planned = c.get("dist.rows_planned", 0)
    if not planned:
        return None
    return 100.0 * c.get("dist.rows_real", 0) / planned
