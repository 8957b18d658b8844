"""The probe's share of its roofline, in %: the least time any exact
count could spend reading its input, over the probe's device time.

The floor is the CSR read once from HBM: ``4 (n + 1)`` bytes of offsets
and ``4 * 2m`` bytes of neighbour ids over the ``m`` unique undirected
edges, at the chip's HBM bandwidth from ``bench/peaks.json``.  A
per-query byte count (the sum of both endpoints' degrees over the
cover-edges) is not used: a kernel that keeps a hub's list in fast
memory would beat it and read over 100%."""

PROGRAM = r"_run_batch"


def floor_bytes(n: int, m: int) -> int:
    return 4 * (n + 1) + 4 * 2 * m


def read(ctx):
    probe_s = ctx.trace.device_s(PROGRAM)
    if probe_s <= 0:
        return None
    counts = ctx.counters["counts"]
    floor_s = floor_bytes(ctx.counters["n"], ctx.counters["m"]) / (
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * counts / probe_s
