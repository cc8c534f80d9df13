"""device.idle_pct.closed: see readers.idle_pct."""
from readers import idle_pct as read  # noqa: F401
