"""Process start to the first timed request: imports, reaching the chip,
weights, compile or compile-cache loads, and the warm-up replays."""


def read(ctx):
    return ctx.setup_s
