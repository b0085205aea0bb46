"""Named host spans and compile counters of one simulation.

``span(name)`` marks a stretch of host work.  It opens a profiler
``TraceAnnotation`` named ``adaptcl.<name>``, so under ``jax.profiler`` the
stretch lies on the trace's host plane, on the clock of the device planes;
and it adds the stretch's wall seconds and a count of one to the running
simulation's accumulator, which ``run_simulation`` returns as
``SimResult.span_s`` and ``SimResult.span_n``.  Spans nest, and a span's
seconds include those of the spans inside it.

While a simulation runs, JAX's own compile events add to the same
accumulator:

* ``compile.trace_lower``: tracing to a jaxpr and lowering to an MLIR
  module (``span_n`` counts the programs traced);
* ``compile.load``: the backend compile, which on a warm persistent compile
  cache is mostly the read of the cache;
* ``compile.cache_miss``: persistent-cache misses (a count in ``span_n``
  only).

``count(name)`` adds a count alone: ``worker.LocalTrainer._call_cached``
counts ``program_cache.hit`` and ``program_cache.miss``, its lookups of a
new signature in the process's table of built programs.

A trace nested inside another (a jitted helper traced while its caller is
traced) is counted once, inside its caller's seconds.  With no simulation
running, spans still annotate the profiler and count nothing.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax

__all__ = ["PREFIX", "Spans", "span", "count", "collect"]

PREFIX = "adaptcl."

# JAX compile events (jax._src.dispatch) -> (span name, counts a program)
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("compile.trace_lower", 1),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("compile.trace_lower", 0),
    "/jax/core/compile/backend_compile_duration": ("compile.load", 1),
}
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class Spans:
    """Seconds and counts per span name of one simulation."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._intervals: Dict[str, List[Tuple[float, float, int]]] = {}

    def add(self, name: str, seconds: float, n: int = 1) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        self.counts[name] = self.counts.get(name, 0) + n

    def add_interval(self, name: str, start: float, end: float, n: int) -> None:
        """``add`` for an event reported with its start and end: the
        intervals of ``name`` it encloses, reported before it, come out
        again, so nested events count once."""
        ivs = self._intervals.setdefault(name, [])
        inner, inner_n = 0.0, 0
        while ivs and ivs[-1][0] >= start:
            s, e, k = ivs.pop()
            inner += e - s
            inner_n += k
        ivs.append((start, end, n))
        self.add(name, end - start - inner, n - inner_n)

    def total(self, prefix: str) -> float:
        """Seconds of every span whose name starts with ``prefix``."""
        return sum(v for k, v in self.seconds.items() if k.startswith(prefix))


_current: Optional[Spans] = None
_listening = False


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Annotate and time the enclosed host work as ``adaptcl.<name>``."""
    with jax.profiler.TraceAnnotation(PREFIX + name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if _current is not None:
                _current.add(name, time.perf_counter() - t0)


def count(name: str) -> None:
    """Count one ``name`` in the running simulation's ``span_n`` (with no
    seconds)."""
    if _current is not None:
        _current.add(name, 0.0)


def _on_time_span(event: str, start: float, end: float, **_) -> None:
    hit = _COMPILE_EVENTS.get(event)
    if hit is not None and _current is not None:
        _current.add_interval(hit[0], start, end, hit[1])


def _on_event(event: str, **_) -> None:
    if event == _CACHE_MISS:
        count("compile.cache_miss")


def _listen() -> None:
    """Register the compile listeners once per process (other listeners,
    such as a harness's own, are left alone)."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


@contextlib.contextmanager
def collect() -> Iterator[Spans]:
    """A fresh accumulator for the spans and compile events until exit."""
    global _current
    _listen()
    outer, acc = _current, Spans()
    _current = acc
    try:
        yield acc
    finally:
        _current = outer
