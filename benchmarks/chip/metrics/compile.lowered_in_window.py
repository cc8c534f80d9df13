"""compile.lowered_in_window: see spanclock.lowered_in_window."""
from spanclock import lowered_in_window as read  # noqa: F401
