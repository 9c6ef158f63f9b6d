"""Host seconds per MoE step in the expert stages: the ``serve.moe.stage``
spans of stage ``gate_up`` and ``down`` in the window (each covers the
engine's replays of that stage), over its steps (one
``serve.moe.route`` each).  ``None`` where the program records no such
spans."""


def read(ctx):
    from repro.obs.tracer import TRACER

    lo, hi = ctx.window.t0, ctx.window.t1
    spans = [
        e for e in TRACER.events
        if e["kind"] == "span" and e["clock"] == "wall"
        and e["name"] in ("serve.moe.stage", "serve.moe.route")
        and e["t0"] >= lo and e["t1"] <= hi
    ]
    steps = sum(1 for e in spans if e["name"] == "serve.moe.route")
    if not steps:
        return None
    return sum(
        e["t1"] - e["t0"] for e in spans
        if e["name"] == "serve.moe.stage" and e["attrs"].get("stage") in ("gate_up", "down")
    ) / steps
