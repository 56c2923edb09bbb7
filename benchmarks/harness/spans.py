"""Host spans recorded by the benchmark's own loop around its calls into each
layer: ``time.perf_counter`` pairs kept in memory, and a
``jax.profiler.TraceAnnotation`` of the same name (``bench:<name>``) so that,
while the profiler runs, the span lands on the trace's clock too.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple

PREFIX = "bench:"


class Spans:
    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.rows: List[Tuple[str, float, float]] = []   # name, start, end

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(PREFIX + name):
            yield
        self.rows.append((name, t0, time.perf_counter()))

    def total_ms(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, t0, t1 in self.rows:
            out[name] = out.get(name, 0.0) + (t1 - t0) * 1e3
        return out
