"""fetch.device_ms_per_call: device milliseconds per engine call under the
``fetch/`` scope (span gathers and cache-slot writes).
See spanclock.scope_ms_per_call."""
from spanclock import scope_ms_per_call


def read(ctx):
    return scope_ms_per_call(ctx, "fetch/")
