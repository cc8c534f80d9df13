"""rerank.device_ms_per_call: device milliseconds per engine call under the
``rerank/`` scope (the int8 tier's row gather and re-rank).
See spanclock.scope_ms_per_call."""
from spanclock import scope_ms_per_call


def read(ctx):
    return scope_ms_per_call(ctx, "rerank/")
