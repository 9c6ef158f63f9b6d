"""Share of the window's requests whose fixed-point scale found ``W``'s int32
residues already on the device: the sum of ``w_hits`` over the sum of
``requests`` of the engine's ``serve.encode`` spans in the window.  ``None``
where the program's spans carry no such counters."""


def read(ctx):
    from repro.obs.tracer import TRACER

    lo, hi = ctx.window.t0, ctx.window.t1
    spans = [
        e["attrs"] for e in TRACER.events
        if e["kind"] == "span" and e["clock"] == "wall" and e["name"] == "serve.encode"
        and e["t0"] >= lo and e["t1"] <= hi
        and "w_hits" in e["attrs"] and "requests" in e["attrs"]
    ]
    requests = sum(a["requests"] for a in spans)
    if not requests:
        return None
    return sum(a["w_hits"] for a in spans) / requests
