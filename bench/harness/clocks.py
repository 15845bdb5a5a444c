"""Compile accounting, copied from the program's ``chip_smoke.py``
``CompileClock``: seconds JAX spends tracing, lowering and compiling (or
loading a program from the persistent cache), from its monitoring
events, plus how many backend compiles happened."""
from __future__ import annotations

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    def __init__(self):
        import jax
        self.total = 0.0
        self.n = 0                  # backend compiles (or cache loads)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in EVENTS:
            self.total += duration
            if event == EVENTS[2]:
                self.n += 1
