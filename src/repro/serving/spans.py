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
it serves without taking it as a parameter; ``stamp(field, value)`` sets
a field of that record (the model stage's padded batch, ``model_bucket``).
The same binding carries the engine's ``on_dispatch`` call, which the
stage code makes through ``dispatched()`` once the window's kernel is
queued on the device.

Names stay bare (``muse.models.fetch``), with no per-call argument: the
trace is reduced by event name.
"""
from __future__ import annotations

import threading
from time import perf_counter
from typing import Callable

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
    """Make ``record`` the current thread's window record in the block,
    and ``on_dispatch`` the call that ``dispatched`` makes."""

    __slots__ = ("record", "on_dispatch", "_previous")

    def __init__(self, record: dict,
                 on_dispatch: Callable[[], None] | None = None) -> None:
        self.record = record
        self.on_dispatch = on_dispatch

    def __enter__(self) -> dict:
        self._previous = (getattr(_local, "record", None),
                          getattr(_local, "on_dispatch", None))
        _local.record, _local.on_dispatch = self.record, self.on_dispatch
        return self.record

    def __exit__(self, *exc) -> None:
        _local.record, _local.on_dispatch = self._previous


def stamp(field: str, value) -> None:
    """Set ``field`` of the current thread's window record, if it has none
    yet: the live call of a stage stamps before its shadows."""
    record = getattr(_local, "record", None)
    if record is not None:
        record.setdefault(field, value)


def dispatched() -> None:
    """The current thread's window has its kernel queued on the device:
    make the bound ``on_dispatch`` call, once per binding (the shadow
    dispatches that follow in the same stage make none)."""
    fn = getattr(_local, "on_dispatch", None)
    if fn is not None:
        _local.on_dispatch = None
        fn()
