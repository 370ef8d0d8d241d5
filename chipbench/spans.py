"""The benchmark's own spans around its calls into each layer.

Kept in memory on the host's monotonic clock and, while a profiler trace is
running (``harness.Tracer`` starts it through the program's capture control),
mirrored as ``jax.profiler.TraceAnnotation`` so that the device trace's idle
gaps can be attributed to what the host was doing. Spans inside
the program are the program's (``tpuft::...``); these carry the prefix
``chipbench/``.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Tuple


class SpanLog:
    """Reads ``time.monotonic``, the clock of the program's own ``_Span``, so a
    capture's ``clock`` anchors lay both kinds of span onto one timeline."""

    def __init__(self) -> None:
        self.spans: List[Tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax.profiler

        start = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, start, time.monotonic()))

    def totals(self, since: float, until: float) -> Dict[str, Dict[str, float]]:
        """{name: {"sum": seconds, "count": n}} of the spans that START in
        [since, until)."""
        out: Dict[str, Dict[str, float]] = {}
        for name, start, end in self.spans:
            if since <= start < until:
                slot = out.setdefault(name, {"sum": 0.0, "count": 0})
                slot["sum"] += end - start
                slot["count"] += 1
        return out
