"""Share of the traced window, in %, in which no op ran on a chip while
the mesh cell ran: ``1 - busy / window``, busy being the union of each
chip's op intervals, averaged over the chips' device planes."""


def read(ctx):
    t = ctx.trace
    if t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
