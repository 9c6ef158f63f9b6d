"""Median wall time of the program's ``runtime.replay`` span in the window:
shares, exchange, device-to-host copy, host decode and subset search."""
from bench.counts import percentile


def read(ctx):
    if not ctx.replays:
        return None
    return percentile([t1 - t0 for t0, t1 in ctx.replay_spans], 50)
