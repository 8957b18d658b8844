"""Peak device memory of the uniform count run, in GB (1e9 bytes), as
``memory_stats()["peak_bytes_in_use"]`` gives it after the window."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
