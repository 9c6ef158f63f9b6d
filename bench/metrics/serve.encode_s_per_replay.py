"""Host seconds per replay in the engine's ``serve.encode`` spans: the batch's
fixed-point scales, the encoded activations and the int64 stack of ``W``."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "serve.encode")
