"""repro.obs: the engine-wide flight recorder (DESIGN.md section 11).

Four pieces, all removable:

  * `MetricsRegistry` (obs/registry.py) — counters, gauges, pow2-bucketed
    latency histograms with p50/p95/p99 extraction; registries merge
    (per-bucket integer addition — the same discipline that makes the
    sketches shard-friendly).
  * `span` / `instant` tracing — `span` IS `jax.profiler.TraceAnnotation`
    and `instant` a zero-length one, so a profiler capture
    (`jax.profiler.trace(dir)`, or a client of `start_server`) holds the
    program's spans beside the device ops, on one clock, with keyword
    args as event stats; outside a session a span costs about a
    microsecond and records nothing.  Names are `<module>.<what>` and
    args ints or strings: readers key on them (bench/spans.py).
    runtime.faultinject crash-point crossings appear as instants.
  * compile accounting — one `jax.monitoring` listener counts JAX's
    compile phases into the process-default registry
    (`jax_compiles_total{phase}`, `jax_compile_seconds_total{phase}`) and
    marks each with an `obs.compile` instant carrying its duration.
  * exporters — `snapshot()`, `render_prom()` (Prometheus text format),
    and the `QueryEngine.stats()` facade built on them.

The on/off contract: REPRO_OBS=0 (or "false"/"off") in the environment
disables the whole layer at import.  Disabled, `new_registry()` returns
the shared `NULL_REGISTRY` (all instruments are constant no-ops) and
`span`/`instant` are rebound to no-op CLOSURES — instrumented code runs
bit-identically, compiles zero additional graphs, and pays one attribute
lookup plus an empty call per site (the CI overhead guard bounds the
enabled path too).  `configure(enabled)` flips the switch at runtime for
tests; call sites must access `obs.span` through the module attribute
(every in-repo site does) for the rebind to take effect.
"""

from __future__ import annotations

import os

import jax
from jax.profiler import TraceAnnotation as _TraceAnnotation

from repro.obs.registry import (Counter, Gauge, Histogram,  # noqa: F401
                                MetricsRegistry, NULL_REGISTRY,
                                NullRegistry)
from repro.runtime import faultinject as _faultinject

__all__ = [
    "MetricsRegistry", "NullRegistry", "NULL_REGISTRY",
    "Counter", "Gauge", "Histogram",
    "span", "instant", "enabled", "configure", "new_registry",
    "get_registry", "render_prom", "snapshot", "COMPILE_EVENTS",
]

# JAX's compile phases, as jax.monitoring names them: tracing to a jaxpr,
# lowering to MLIR, and the backend compile (or persistent-cache load)
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _noop_span(name, **args):
    return _NULL_SPAN


def _noop_instant(name, **args):
    return None


def _instant(name: str, **args) -> None:
    """A zero-length span at this point of the calling thread."""
    with _TraceAnnotation(name, **args):
        pass


_enabled = os.environ.get("REPRO_OBS", "1").strip().lower() not in (
    "0", "false", "off")
_default_registry: MetricsRegistry | None = None

# rebound by configure(); import-time defaults set at the bottom
span = _noop_span
instant = _noop_instant


def enabled() -> bool:
    return _enabled


def configure(on: bool) -> None:
    """Flip the module switch at runtime (tests; production uses the
    REPRO_OBS env var read at import).  Registries already handed out keep
    their mode — only objects created AFTER the flip see it."""
    global _enabled, span, instant
    _enabled = bool(on)
    if _enabled:
        span = _TraceAnnotation
        instant = _instant
        _faultinject.set_observer(_crash_point_instant)
    else:
        span = _noop_span
        instant = _noop_instant
        _faultinject.set_observer(None)


def _crash_point_instant(point: str) -> None:
    """faultinject observer: each crash-point crossing becomes an instant
    event, so durability boundaries are visible inside migration/save
    spans in a profiler capture."""
    _instant("crash_point", point=point)


def _on_compile_event(event: str, duration: float, **_) -> None:
    """jax.monitoring listener: count each compile phase into the
    process-default registry and mark its end with an `obs.compile`
    instant whose `us` arg is its duration, so a profile shows where the
    compile stalls fell.  Runs on the compiling thread."""
    phase = COMPILE_EVENTS.get(event)
    if phase is None or not _enabled:
        return
    reg = get_registry()
    reg.counter("jax_compiles_total", phase=phase).inc()
    reg.counter("jax_compile_seconds_total", phase=phase).inc(duration)
    _instant("obs.compile", phase=phase, us=int(duration * 1e6))


def new_registry() -> MetricsRegistry | NullRegistry:
    """A fresh registry under the current switch — what QueryEngine builds
    its per-engine registry from (NULL_REGISTRY when disabled, so every
    instrument call in the engine is a shared no-op)."""
    return MetricsRegistry() if _enabled else NULL_REGISTRY


def get_registry() -> MetricsRegistry | NullRegistry:
    """The process-default registry (created on first use) — for module
    code with no engine to hang metrics on.  Engines default to their OWN
    registries so per-engine stats stay separable; merge them into this
    one to get a process-wide view."""
    global _default_registry
    if not _enabled:
        return NULL_REGISTRY
    if _default_registry is None:
        _default_registry = MetricsRegistry()
    return _default_registry


def render_prom(registry=None) -> str:
    """Prometheus text format of `registry` (default: the process-default
    registry)."""
    return (registry if registry is not None else get_registry()
            ).render_prom()


def snapshot(registry=None) -> dict:
    """Plain-dict snapshot of `registry` (default: the process-default)."""
    return (registry if registry is not None else get_registry()).snapshot()


configure(_enabled)
# jax.monitoring has no unregister: the listener is installed once and
# checks the switch itself
jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
