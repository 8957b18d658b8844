"""Device seconds of Algorithm 2 per distributed count on the slowest
chip: the ``_tc_distributed`` program's time on each chip's device
plane in the traced window, the largest plane, over the counts made in
it.  ``None`` where no program of that name ran."""

from bench import chips

PROGRAM = r"_tc_distributed"


def read(ctx):
    s = chips.slowest_s(ctx.trace, PROGRAM)
    return s / ctx.counters["counts"] if s > 0 else None
