"""The probe's share of its roofline, in %, for the uniform graph:
``probe_roofline.count`` (the CSR read once from HBM, over the probe's
device time) for the cell that reports ``count_s.urand``."""

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
