"""Open loop: requests per replay over ``max_batch``, all requests over all
replays of the window (from each request's ``replay`` index)."""


def read(ctx):
    done = ctx.done
    if ctx.cell.traffic.loop != "open" or not done:
        return None
    replays = len({r.replay for r in done})
    return len(done) / replays / ctx.cell.deployment.max_batch
