"""Open loop: 90th percentile of the same latencies as ``latency_p50_s``."""
from bench.counts import percentile


def read(ctx):
    if ctx.cell.traffic.loop != "open" or not ctx.done:
        return None
    return percentile([r.completion - r.due for r in ctx.done], 90)
