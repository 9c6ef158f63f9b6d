"""Host seconds per replay in ``runtime.fetch``: the device-to-host copy of the
I-evaluations, once the device is done."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "runtime.fetch")
