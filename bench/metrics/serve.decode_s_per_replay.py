"""Host seconds per replay in the engine's ``serve.decode`` spans: the decoded
field values and each request's float decode of ``Y``."""
from bench.spans import per_replay


def read(ctx):
    return per_replay(ctx, "serve.decode")
