"""Device seconds per replay of the programs launched under the span
``protocol.phase2`` (worker multiply and degree reduction), from the traced
window."""
from bench.spans import device_s_per_replay


def read(ctx):
    return device_s_per_replay(ctx, "protocol.phase2")
