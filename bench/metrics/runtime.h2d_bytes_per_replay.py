"""Bytes handed to the device per replay: the ``bytes`` of ``runtime.upload``
(the int32 operands sent from the host: the activations' A alone, since
``W``'s residues are already on the device)."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "runtime.upload", "bytes")
