"""serve.decode_ms_per_call: device milliseconds per engine call under the
``serve/decode`` scope (the per-pair span decode).
See spanclock.scope_ms_per_call."""
from spanclock import scope_ms_per_call


def read(ctx):
    return scope_ms_per_call(ctx, "serve/decode")
