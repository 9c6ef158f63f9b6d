"""Open loop: median over every request due in the window of the time from
its due time to the end of the ``engine.run()`` call that handed the
caller its decoded ``Y``, on the benchmark's clock."""
from bench.counts import percentile


def read(ctx):
    if ctx.cell.traffic.loop != "open" or not ctx.done:
        return None
    return percentile([r.completion - r.due for r in ctx.done], 50)
