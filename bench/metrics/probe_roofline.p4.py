"""Algorithm 2's share of its roofline, in %: the least time any exact
count over the mesh could spend reading its input, over the program's
device time on the slowest chip.

The floor is the CSR read once from HBM, ``4 (n + 1)`` bytes of offsets
and ``4 * 2m`` bytes of neighbour ids over the ``m`` unique undirected
edges, shared over the ``chips`` chips, each at the HBM bandwidth of
``bench/peaks.json``.  It is counted from the graph and not from the
implementation, so no later change of the program makes it stale."""

from bench import chips

PROGRAM = r"_tc_distributed"


def floor_bytes(n: int, m: int) -> int:
    return 4 * (n + 1) + 4 * 2 * m


def read(ctx):
    slowest = chips.slowest_s(ctx.trace, PROGRAM)
    if slowest <= 0:
        return None
    c = ctx.counters
    floor_s = floor_bytes(c["n"], c["m"]) / (
        c["chips"] * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * c["counts"] / slowest
