"""1 - device busy / window, from the traced window (mean over the cell's
chips), as a ratio."""


def read(ctx):
    if ctx.device is None or ctx.device.window_s <= 0:
        return None
    busy = ctx.device.busy_s(ctx.device.chips[: ctx.cell.chips])
    if busy <= 0:
        return None
    return 1.0 - busy / ctx.device.window_s
