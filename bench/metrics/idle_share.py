"""Device: share of the traced window with no operation running on the
chip (1 - union of busy intervals / window)."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
