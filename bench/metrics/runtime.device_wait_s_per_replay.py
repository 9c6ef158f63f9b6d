"""Host seconds per replay in ``runtime.device_wait``: blocked until the
device has produced the I-evaluations (all of Phase 1 and Phase 2 still
queued)."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "runtime.device_wait")
