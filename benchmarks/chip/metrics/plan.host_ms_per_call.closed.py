"""plan.host_ms_per_call.closed: see readers.plan_host_ms_per_call."""
from readers import plan_host_ms_per_call as read  # noqa: F401
