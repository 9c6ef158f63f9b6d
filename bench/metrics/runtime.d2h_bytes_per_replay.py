"""Bytes copied back to the host per replay: the ``bytes`` of ``runtime.fetch``
(the I-evaluations of every worker)."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "runtime.fetch", "bytes")
