"""List entries one chip's hedge plan gathers in one distributed count,
in 10⁹: the program's ``dist.entries_planned`` counter (each count's
rounds × Σ rows × (d_cand + d_targ) of the plan every chip runs) over
``dist.counts``, from ``repro.obs``.  It shows whether the plan's target
bands and row cap engaged.  ``None`` where the program keeps no such
counter."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    counts = c.get("dist.counts", 0)
    entries = c.get("dist.entries_planned", 0)
    if not counts or not entries:
        return None
    return entries / counts / 1e9
