"""Share of the traced window, in %, in which no op ran on the device
while the uniform graph was counted: ``1 - busy / window``."""


def read(ctx):
    t = ctx.trace
    if t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
