"""The harness's own spans: each is a ``jax.profiler.TraceAnnotation``
(so that a traced run has it on the profiler's clock, beside the device
operations) and a host-clock record kept in memory until the run ends.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.record: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.record.append((name, t0, time.perf_counter()))

    def totals(self, prefix: str) -> dict[str, float]:
        """Seconds by span name, of the spans whose name starts so."""
        out: dict[str, float] = {}
        for name, t0, t1 in self.record:
            if name.startswith(prefix):
                out[name] = out.get(name, 0.0) + (t1 - t0)
        return out
