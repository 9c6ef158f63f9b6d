"""Host seconds per replay in the engine's ``serve.encode`` spans: the batch's
fixed-point scales, the encoded activations, and the stack on the device of
``W``'s resident int32 residues (uploaded once per scale, at its first
request)."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "serve.encode")
