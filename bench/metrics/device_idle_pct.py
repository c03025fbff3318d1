"""Device idle share of the traced window, in percent: 100 x (1 - the
union of device-operation intervals / the window)."""


def read(ctx):
    if ctx.busy_s is None or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
