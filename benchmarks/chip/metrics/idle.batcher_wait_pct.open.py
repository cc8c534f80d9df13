"""idle.batcher_wait_pct.open: see spanclock.idle_wait_window_pct."""
from spanclock import idle_wait_window_pct as read  # noqa: F401
