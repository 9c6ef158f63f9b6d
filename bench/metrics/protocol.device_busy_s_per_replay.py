"""Device busy time in the traced window (union of op intervals, mean over
the cell's chips) per replay of the window."""


def read(ctx):
    if ctx.device is None or not ctx.replays:
        return None
    chips = ctx.device.chips[: ctx.cell.chips]
    busy = ctx.device.busy_s(chips)
    return busy / ctx.replays if busy > 0 else None
