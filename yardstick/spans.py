"""The benchmark's own spans: name, start, end on the host's clock, kept in
memory, and, while the profiler runs, written into its trace as
``TraceAnnotation`` so that they sit on the device events' clock."""

from __future__ import annotations

import contextlib
import time

PREFIX = "ys."  # how the reduction tells these from JAX's own host events


class Spans:
    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            import jax

            ctx = jax.profiler.TraceAnnotation(PREFIX + name)
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = 0.0) -> list[float]:
        return [b - a for n, a, b in self.rows if n == name and a >= since]
