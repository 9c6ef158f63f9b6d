"""Closed loop: activation rows of every request decoded in the window,
over the window's wall length (first ``run()`` start to the end of the
first ``run()`` that ends after ``--seconds``)."""


def read(ctx):
    if ctx.cell.traffic.loop != "closed" or ctx.window.seconds <= 0:
        return None
    return sum(r.rows for r in ctx.done) / ctx.window.seconds
