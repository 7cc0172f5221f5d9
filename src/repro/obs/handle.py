"""The ``Obs`` handle: one object carrying a tracer + metrics registry
through the runtime.

Every instrumented layer (scheduler, serve engine, router, health
monitors, launch drivers) takes an optional ``obs`` parameter and defaults
to :data:`NOOP_OBS` — a shared disabled handle whose tracer and metrics
are no-ops, so observability costs nothing unless explicitly switched on
with :meth:`Obs.on`.  Hot paths additionally guard span construction with
``if obs.enabled:`` so the disabled path never even builds args dicts.

:func:`phase` names a stretch of host work on the JAX profiler's host plane,
on the device trace's clock, and on the handle's tracer when it is live.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator, Optional, Union

from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import NullTracer, Tracer


class _NullMetrics:
    """No-op :class:`MetricsRegistry` twin for the disabled handle."""

    def __init__(self):
        self._counter = Counter("null")
        self._gauge = Gauge("null")
        self._histogram = Histogram("null", keep=1)

    def counter(self, name: str) -> Counter:
        """A shared throwaway counter."""
        return self._counter

    def gauge(self, name: str) -> Gauge:
        """A shared throwaway gauge."""
        return self._gauge

    def histogram(self, name: str) -> Histogram:
        """A shared throwaway histogram."""
        return self._histogram

    def snapshot(self) -> dict:
        """Always empty."""
        return {}

    def write_snapshot(self, path: str) -> None:
        """No-op."""

    def reset(self) -> None:
        """No-op."""


@dataclasses.dataclass
class Obs:
    """Observability handle: a span :class:`~repro.obs.trace.Tracer` plus
    a :class:`~repro.obs.metrics.MetricsRegistry`, passed together through
    the serve/search/fleet layers.

    ``enabled`` is the hot-path guard: instrumented code checks it before
    building span arguments, so a disabled handle's cost is one attribute
    read per site."""

    tracer: Union[Tracer, NullTracer]
    metrics: Union[MetricsRegistry, _NullMetrics]
    enabled: bool = True

    @classmethod
    def on(cls, capacity_per_thread: int = 65536,
           metrics: Optional[MetricsRegistry] = None) -> "Obs":
        """A live handle: fresh tracer, fresh registry (or the one passed
        in, e.g. :func:`repro.obs.metrics.default_registry` to merge with
        process-global search/fleet metrics)."""
        return cls(tracer=Tracer(capacity_per_thread),
                   metrics=metrics if metrics is not None
                   else MetricsRegistry(), enabled=True)

    @classmethod
    def off(cls) -> "Obs":
        """The shared disabled handle (:data:`NOOP_OBS`)."""
        return NOOP_OBS


NOOP_OBS = Obs(tracer=NullTracer(), metrics=_NullMetrics(), enabled=False)


@contextlib.contextmanager
def phase(name: str, obs: Optional[Obs] = None) -> Iterator[None]:
    """A named stretch of host work.

    Always opens ``jax.profiler.TraceAnnotation(name)``, so a profiled run
    shows the span on the host plane beside the device's operations, on
    one clock; with the profiler off it costs about a microsecond.  When
    ``obs`` is given and enabled the same interval is also recorded on its
    tracer (track ``search/host``), so a Chrome export shows it too."""
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        if obs is not None and obs.enabled:
            obs.tracer.complete(name, cat="phase", track="search/host",
                                start=t0, end=time.perf_counter())
