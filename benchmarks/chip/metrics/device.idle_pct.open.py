"""device.idle_pct.open: see readers.idle_pct."""
from readers import idle_pct as read  # noqa: F401
