"""Process-wide count of the programs JAX lowers to XLA.

:data:`COMPILES` listens to JAX's ``jaxpr_to_mlir_module`` duration
event, which fires once for every program not already compiled in this
process: a true compile or a load from the persistent compilation
cache.  It counts only when JAX lowers a program, so it costs nothing
on the serving path and stays on whether or not the tracer is enabled.
``SearchServer.stats()["compiles"]`` reports it; a count that grows
while a server is warm means a new shape reached the device.
"""

from __future__ import annotations

import threading
from typing import Dict

EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Lowerings seen since :meth:`install`, and the seconds they took."""

    def __init__(self) -> None:
        """Create an uninstalled counter at zero."""
        self.n = 0
        self.seconds = 0.0
        self._installed = False
        self._lock = threading.Lock()

    def install(self) -> "CompileCounter":
        """Register the listener with JAX; later calls do nothing."""
        with self._lock:
            if not self._installed:
                import jax
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_event)
                self._installed = True
        return self

    def _on_event(self, event: str, duration: float, **_: object) -> None:
        """Count one lowering (JAX calls this for every duration event)."""
        if event == EVENT:
            with self._lock:
                self.n += 1
                self.seconds += duration

    def snapshot(self) -> Dict[str, float]:
        """``{"n": lowerings, "seconds": time spent lowering them}``."""
        with self._lock:
            return {"n": self.n, "seconds": self.seconds}


#: The process's counter (JAX's listeners are process-global too).
COMPILES = CompileCounter()
