"""idle.host_pct.open: see spanclock.idle_host_pct."""
from spanclock import idle_host_pct as read  # noqa: F401
