"""queue.wait_ms_p50.open: see readers.queue_wait_ms_p50."""
from readers import queue_wait_ms_p50 as read  # noqa: F401
