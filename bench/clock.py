"""Compile seconds, from JAX's own monitoring events (copied from the
repository's chip smoke).  A jit traced inside another reports a span
inside its parent's, so the clock measures the union of the spans."""

from __future__ import annotations

import math
import time

import jax

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading the
    persistent cache), on the perf_counter clock."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def seconds(self, t0: float = -math.inf, t1: float = math.inf) -> float:
        """Compile seconds within [t0, t1]."""
        total, reach = 0.0, t0
        for a, b in sorted(self.spans):
            a, b = max(a, reach), min(b, t1)
            if b > a:
                total += b - a
                reach = b
        return total

    def count(self, t0: float = -math.inf, t1: float = math.inf) -> int:
        """Compile events that ended within [t0, t1]."""
        return sum(1 for _, b in self.spans if t0 <= b <= t1)
