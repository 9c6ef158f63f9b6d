"""``serve.w_resident_hit_share`` in the open-loop cell, where it moves
``latency_p90_s``."""
from bench.harness import METRICS_DIR, load_metric

_READER = load_metric("serve.w_resident_hit_share", "ratio", METRICS_DIR)


def read(ctx):
    return _READER.read(ctx)
