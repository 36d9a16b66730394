"""Observability plane: span tracing + metrics on the simulated event
clock, with a zero-cost no-op default, and named host
spans of the served path on the profiler's clock (``host_span``).

The data plane (storage simulator, resilience chains, cache,
``search_pag``, the serving front-end) reports into whatever tracer /
metrics registry is *currently installed*:

    from repro.obs import observe, Tracer, MetricsRegistry
    tracer, metrics = Tracer(), MetricsRegistry()
    with observe(tracer=tracer, metrics=metrics):
        search_pag(...)            # spans + counters recorded
    tracer.save("trace.json")      # chrome://tracing / ui.perfetto.dev
    print(metrics.snapshot())      # flat {name: value} dict

By default a disabled no-op pair is installed: every instrumentation
site degrades to an attribute lookup plus an empty method call, and
search results / ``SearchStats`` are bit-identical to the uninstrumented
code path (tested in tests/test_obs.py).

``host_span`` is always on: each stage of the served path
(``AnnsFrontend`` -> ``search_pag`` -> ``dataplane``) opens one, named
``anns/<stage>``, with integer stats (bytes, counts). The JAX profiler
records it, beside the device's XLA ops, while a trace is active
(``jax.profiler.trace(dir)``); otherwise it costs about a microsecond.
An installed ``Tracer`` also gets it, on its ``host-wall`` group.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

from jax.profiler import TraceAnnotation

from repro.obs.metrics import NOOP_METRICS, MetricsRegistry
from repro.obs.trace import NOOP_TRACER, WALL_GROUP, Span, Tracer

__all__ = [
    "MetricsRegistry", "SPAN_PREFIX", "Span", "Tracer",
    "get_metrics", "get_tracer", "host_span", "observe",
]

SPAN_PREFIX = "anns/"   # every host span of the served path
HOST_TRACK = "host"     # the Tracer track host spans go on

_tracer: Tracer = NOOP_TRACER
_metrics: MetricsRegistry = NOOP_METRICS


def get_tracer() -> Tracer:
    """The currently-installed tracer (the disabled no-op by default)."""
    return _tracer


def get_metrics() -> MetricsRegistry:
    """The currently-installed metrics registry (no-op by default)."""
    return _metrics


@contextlib.contextmanager
def observe(tracer: Optional[Tracer] = None,
            metrics: Optional[MetricsRegistry] = None) -> Iterator[None]:
    """Install a tracer and/or metrics registry for the dynamic extent
    of the block; either may be omitted (the previous one is kept)."""
    global _tracer, _metrics
    prev_t, prev_m = _tracer, _metrics
    if tracer is not None:
        _tracer = tracer
    if metrics is not None:
        _metrics = metrics
    try:
        yield
    finally:
        _tracer, _metrics = prev_t, prev_m


class host_span:
    """Context manager: one named host span of the served path,
    ``anns/<name>`` on the profiler's clock, with integer ``stats``
    (bytes, counts, nanoseconds) as the event's stats. ``set(**stats)``
    adds stats known only inside the span. With a ``Tracer`` installed,
    the span also goes on its ``host-wall`` group at its real start,
    relative to the tracer's creation. Open one per stage, never one per
    query inside a per-query loop."""

    __slots__ = ("name", "stats", "_ann", "_tracer", "_t0")

    def __init__(self, name: str, **stats: int):
        self.name = SPAN_PREFIX + name
        self.stats = stats
        self._ann = TraceAnnotation(self.name, **stats)

    def set(self, **stats: int) -> None:
        self._ann.set_metadata(**stats)
        self.stats.update(stats)

    def __enter__(self) -> "host_span":
        self._tracer = _tracer if _tracer.enabled else None
        self._ann.__enter__()
        if self._tracer is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        if tr is not None:
            t1 = time.perf_counter()
            tr.span(HOST_TRACK, self.name, self._t0 - tr.t0_wall_s,
                    t1 - self._t0, cat="host", args=dict(self.stats),
                    group=WALL_GROUP)
        self._ann.__exit__(*exc)
