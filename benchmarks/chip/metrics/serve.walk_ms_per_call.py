"""serve.walk_ms_per_call: device milliseconds per engine call under the
``serve/walk`` scope (the in-partition beam walk).
See spanclock.scope_ms_per_call."""
from spanclock import scope_ms_per_call


def read(ctx):
    return scope_ms_per_call(ctx, "serve/walk")
