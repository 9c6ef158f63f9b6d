"""Device seconds per replay of the programs launched under the span
``protocol.phase1.share_batched`` (the Phase-1 shares of both operands), from
the traced window."""
from bench.spans import device_s_per_replay


def read(ctx):
    return device_s_per_replay(ctx, "protocol.phase1.share_batched")
