"""``Obs``: tracer + metrics + (optional) Theorem-1 decomposition as one
handle.  Kept apart from the package ``__init__`` so the engine
(``core.engine``) can import it without importing ``obs.decomp``, which
itself imports ``repro.core``."""
from __future__ import annotations

from typing import Optional

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import SpanTracer


class Obs:
    """Tracer + metrics + (optional) Theorem-1 decomposition, as one
    handle the serving loops and the engine thread through.  Construct
    with ``Obs.on()`` for everything enabled, or default-construct (or
    use ``NULL_OBS``) for the disabled bundle."""

    def __init__(self, tracer: Optional[SpanTracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 decomp=None):
        self.tracer = tracer if tracer is not None \
            else SpanTracer(enabled=False)
        self.metrics = metrics if metrics is not None \
            else MetricsRegistry(enabled=False)
        self.decomp = decomp

    @classmethod
    def on(cls, decomp=None) -> "Obs":
        return cls(SpanTracer(enabled=True), MetricsRegistry(enabled=True),
                   decomp)

    @property
    def enabled(self) -> bool:
        return (self.tracer.enabled or self.metrics.enabled
                or self.decomp is not None)


NULL_OBS = Obs()
