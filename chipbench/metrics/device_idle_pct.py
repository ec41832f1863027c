"""Share of the traced window in which no op ran on the device."""


def read(ctx):
    v = ctx.view
    if not v.ops or v.window_s <= 0:
        return None
    return 100.0 * (1.0 - v.busy_s / v.window_s)
