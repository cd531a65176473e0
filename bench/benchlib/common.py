"""Small pieces the cell drivers share."""
from __future__ import annotations

import numpy as np


def span(name: str):
    """A host span in the profiler's trace (a no-op cost when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts XLA compilations, to show that none happens in the window."""

    def __init__(self):
        import jax
        self.count = 0
        self._on = False

        def listen(event, _duration, **_kw):
            if self._on and "backend_compile" in event:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def reset(self):
        self.count = 0
        self._on = True


def p95(values) -> float | None:
    """95th percentile (linear interpolation), or None with no sample."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95))
