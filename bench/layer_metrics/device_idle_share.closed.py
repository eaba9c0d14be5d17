"""Device: the share of the traced window in which no operation ran, 1 -
(union of op intervals) / window, mean over the cell's chips.  In the
closed loops it is the time the device waits on the host.  Moves
``qps``."""


def read(ctx):
    if ctx.device is None or ctx.device.window_s <= 0:
        return None
    return 1.0 - ctx.device.busy_s / ctx.device.window_s
