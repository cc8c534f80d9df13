"""serve.device_ms_per_call: device time of the graph tier's serve round
(the ``serve_and_merge`` jitted module) per engine call."""
from readers import module_ms_per_call


def read(ctx):
    return module_ms_per_call(ctx, "jit_serve_and_merge")
