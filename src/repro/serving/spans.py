"""Host spans of the serving path, on the profiler's clock.

``with span("muse.<layer>[.<step>]"):`` is a
``jax.profiler.TraceAnnotation`` whenever a profiler runs: an event on the
trace's host plane, on the same clock as the device's operations.  With
none running it is a shared no-op, a fraction of a microsecond.

``with timed(name, field) as s:`` is the same span, also timed on
``time.perf_counter`` (the clock of the engine's window stamps):
``s.seconds`` after the block, and, given a ``field``, its milliseconds
are added to that field of the window record the current thread has
bound (``bind``).  The engine binds each window's ``window_log`` record
around the window's stages, so the server's stage code stamps the window
it serves without taking it as a parameter.

Names stay bare (``muse.models.fetch``), with no per-call argument: the
trace is reduced by event name.
"""
from __future__ import annotations

import threading
from time import perf_counter

from jax.profiler import TraceAnnotation

_local = threading.local()
_tracing = TraceAnnotation.is_enabled   # is a profiler collecting events?


class _Off:
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


_OFF = _Off()


def span(name: str):
    """A host span named ``name`` (a no-op while no profiler runs)."""
    return TraceAnnotation(name) if _tracing() else _OFF


class timed:
    """A span that keeps its duration: ``seconds`` after the block."""

    __slots__ = ("_annotation", "field", "start", "seconds")

    def __init__(self, name: str, field: str | None = None) -> None:
        self._annotation = span(name)
        self.field = field
        self.seconds = 0.0

    def __enter__(self) -> "timed":
        self._annotation.__enter__()
        self.start = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = perf_counter() - self.start
        self._annotation.__exit__(*exc)
        if self.field is not None:
            record = getattr(_local, "record", None)
            if record is not None:
                record[self.field] = record.get(self.field, 0.0) \
                    + self.seconds * 1e3


class bind:
    """Make ``record`` the current thread's window record in the block."""

    __slots__ = ("record", "_previous")

    def __init__(self, record: dict) -> None:
        self.record = record

    def __enter__(self) -> dict:
        self._previous = getattr(_local, "record", None)
        _local.record = self.record
        return self.record

    def __exit__(self, *exc) -> None:
        _local.record = self._previous
