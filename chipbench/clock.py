"""Seconds JAX spends tracing, lowering and compiling, and the number
of backend compiles, from JAX's own monitoring events."""
from __future__ import annotations

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == COMPILE_EVENTS[-1]

    def mark(self):
        return self.seconds, self.compiles
