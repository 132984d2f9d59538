"""Host spans of the serving scheduler (DESIGN.md §10).

:func:`span` is the one way the program opens a span.  It returns a
``jax.profiler.TraceAnnotation``: while a profiler trace is recording
(``jax.profiler.trace(dir)`` or ``start_trace``), the span lands on the
``/host:CPU`` plane on the same clock as the device's ops, so each idle gap
between two device executions can be named after the scheduler phase the
host was in.  With no trace recording a span costs about a microsecond.

The module reads no clock and keeps no buffer: the profiler holds the
spans and writes them out at ``stop_trace``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

from jax.profiler import TraceAnnotation

__all__ = ["span"]

Counters = Callable[[], Dict[str, float]]


class _Span(TraceAnnotation):
    """A ``TraceAnnotation`` whose metadata is read when it closes."""

    def __init__(self, name: str, counters: Counters):
        super().__init__(name)
        self._counters = counters

    def __exit__(self, *exc):
        if self.is_enabled():
            self.set_metadata(**self._counters())
        return super().__exit__(*exc)


def span(name: str, counters: Optional[Counters] = None) -> TraceAnnotation:
    """Context manager for one host span named ``name`` (DESIGN.md §10).

    ``counters`` is a zero-argument callable returning a dict of numbers.
    It is called when the span closes, and only while a trace is
    recording; its dict becomes the span's metadata (the xplane event's
    ``stats``), so it may read state the span's body changed."""
    if counters is None:
        return TraceAnnotation(name)
    return _Span(name, counters)
