"""The engine's own host work per replay: the benchmark's wall span around
each ``engine.run()`` minus the ``runtime.replay`` spans inside it, over
the replays of the window (fixed-point scales, ``W`` encode and stack,
batching, the float decode of ``Y``)."""


def read(ctx):
    if not ctx.replays:
        return None
    runs = sum(t1 - t0 for t0, t1 in ctx.window.runs)
    replays = sum(t1 - t0 for t0, t1 in ctx.replay_spans)
    return (runs - replays) / ctx.replays
