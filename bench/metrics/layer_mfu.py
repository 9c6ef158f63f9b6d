"""The whole step's share of the chips' bf16 peak, in %: ``rows_per_s``
times the plaintext FLOPs of one row (2 k out) over chips x peak.  It
counts the work the user asks for, not the protocol's."""
from bench import counts


def read(ctx):
    cell = ctx.cell
    if cell.traffic.loop != "closed" or ctx.window.seconds <= 0:
        return None
    rows_per_s = sum(r.rows for r in ctx.done) / ctx.window.seconds
    flops = rows_per_s * counts.request_flops(1, cell.deployment.k, cell.deployment.out)
    return 100.0 * flops / (cell.chips * ctx.peaks["bf16_flops_per_s"])
