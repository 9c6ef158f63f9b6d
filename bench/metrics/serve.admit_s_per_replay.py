"""Host seconds per replay in the engine's ``serve.admit`` spans: picking the
next trace, the pipeline's ``ready_at``, and admission with the pool estimate
it refits."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "serve.admit")
