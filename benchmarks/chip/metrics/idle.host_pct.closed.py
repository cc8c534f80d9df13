"""idle.host_pct.closed: see spanclock.idle_host_pct."""
from spanclock import idle_host_pct as read  # noqa: F401
