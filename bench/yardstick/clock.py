"""Counts JAX compilations by the compile-duration events JAX reports.

Copied from ``chip_smoke.py`` ``CompileClock`` (the event names and the
listener), counting the events where the original sums their seconds, so
that the benchmark can say how many programs were traced or compiled
inside its measured window.
"""

from __future__ import annotations

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Counts JAX's compile-duration events (a process-wide listener)."""

    def __init__(self):
        import jax
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.events += 1
