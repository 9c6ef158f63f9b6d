"""The whole step's share of the chips' bf16 peak, in %: the plaintext
FLOPs of every request decoded in the window (the layer's
``request_flops``; 2 rows k out for one projection) over the window's
length, over chips x peak.  It counts the work the user asks for, not the
protocol's."""


def read(ctx):
    cell = ctx.cell
    if cell.traffic.loop != "closed" or ctx.window.seconds <= 0:
        return None
    flops = sum(cell.layer.request_flops(cell.deployment, r.rows) for r in ctx.done)
    return 100.0 * flops / ctx.window.seconds / (cell.chips * ctx.peaks["bf16_flops_per_s"])
