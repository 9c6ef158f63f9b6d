"""Share of the rows the MoE's private products launched that were
padding: the ``padded_rows`` of the ``serve.moe.stage`` spans in the
window over their ``launched_rows`` (row-bucket padding, and the rows of
entries that pad a replay's batch).  ``None`` where the program records
no such spans."""


def read(ctx):
    from repro.obs.tracer import TRACER

    lo, hi = ctx.window.t0, ctx.window.t1
    stages = [
        e["attrs"] for e in TRACER.events
        if e["kind"] == "span" and e["clock"] == "wall" and e["name"] == "serve.moe.stage"
        and e["t0"] >= lo and e["t1"] <= hi
        and "launched_rows" in e["attrs"] and "padded_rows" in e["attrs"]
    ]
    launched = sum(a["launched_rows"] for a in stages)
    if not launched:
        return None
    return sum(a["padded_rows"] for a in stages) / launched
