"""Share of the window's replays decoded on the device: the ``runtime.fetch``
spans in the window whose ``decode`` is ``"device"`` (only Y's coefficients
copied back) over all of them.  ``None`` where no such span carries a
``decode`` attribute."""


def read(ctx):
    from repro.obs.tracer import TRACER

    lo, hi = ctx.window.t0, ctx.window.t1
    decodes = [
        e["attrs"].get("decode") for e in TRACER.events
        if e["kind"] == "span" and e["clock"] == "wall" and e["name"] == "runtime.fetch"
        and e["t0"] >= lo and e["t1"] <= hi
    ]
    if not any(d is not None for d in decodes):
        return None
    return sum(d == "device" for d in decodes) / len(decodes)
