"""Host seconds per MoE step at the client: the ``serve.moe.route``,
``serve.moe.swiglu`` and ``serve.moe.combine`` spans in the window, over
its steps (one ``serve.moe.route`` each).  ``None`` where the program
records no such spans."""

CLIENT = ("serve.moe.route", "serve.moe.swiglu", "serve.moe.combine")


def read(ctx):
    from repro.obs.tracer import TRACER

    lo, hi = ctx.window.t0, ctx.window.t1
    spans = [
        e for e in TRACER.events
        if e["kind"] == "span" and e["clock"] == "wall" and e["name"] in CLIENT
        and e["t0"] >= lo and e["t1"] <= hi
    ]
    steps = sum(1 for e in spans if e["name"] == "serve.moe.route")
    if not steps:
        return None
    return sum(e["t1"] - e["t0"] for e in spans) / steps
