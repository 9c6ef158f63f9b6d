"""Host seconds per replay in ``runtime.upload``: the operands reduced mod p,
cast to int32 and handed to the device."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "runtime.upload")
