"""Peak device memory of the run on its fullest chip, in GB (1e9
bytes): the largest ``memory_stats()["peak_bytes_in_use"]`` over the
cell's chips after the window, as the harness reads it."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
