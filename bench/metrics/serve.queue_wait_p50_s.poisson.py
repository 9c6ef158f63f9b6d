"""Open loop: median over the window's requests of the time from the due
time to the start of the ``engine.run()`` call that served it."""
from bench.counts import percentile


def read(ctx):
    if ctx.cell.traffic.loop != "open" or not ctx.requests:
        return None
    return percentile([r.run_start - r.due for r in ctx.requests], 50)
