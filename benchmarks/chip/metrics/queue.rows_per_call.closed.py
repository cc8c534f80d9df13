"""queue.rows_per_call.closed: see readers.rows_per_call."""
from readers import rows_per_call as read  # noqa: F401
