"""Spans of the serving loop, on the host's monotonic clock.

A :class:`Spans` recorder keeps one row per closed span,
``(name, start_ns, end_ns, parent, attrs)``, in a bounded deque in
memory. Times are ``time.monotonic_ns()``, the clock
``StreamingBayesSplitEdge.serve()`` stamps its start and each result's
``emit_s`` on; ``serve()`` stores its start on the recorder as ``t0_ns``,
so a request's due time ``t0_ns + arrivals[i] * time_scale`` can be set
against the rows. ``parent`` is ``(name, start_ns)`` of the span that was
open around this one (that span's own row), or ``None``.

Each span also enters a ``jax.profiler.TraceAnnotation`` of the same
name, with the span's scalar attributes, so that a profiler trace taken
while the server runs shows it on the host plane beside the device's
operations. Attributes that are lists stay in memory. A span opened with
``annotate=False`` stays in memory too: ``serve.round``, which holds
every other span, would otherwise be the host event that covers each
idle gap of the device most, and hide the span inside it that names
the gap.

The server takes a recorder as ``spans=``; without one it uses
:data:`NULL`, whose ``span()`` returns one shared
``contextlib.nullcontext()`` and records nothing.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Optional

import jax

_NULL_CONTEXT = contextlib.nullcontext()
_SCALARS = (bool, int, float, str)


class _Span:
    """One open span; re-entered by :meth:`Spans.gap` as a new segment."""

    __slots__ = ("rec", "name", "attrs", "annotate", "start", "parent",
                 "ann")

    def __init__(self, rec: "Spans", name: str, attrs: dict,
                 annotate: bool):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.annotate = annotate

    def __enter__(self):
        rec = self.rec
        top = rec._open[-1] if rec._open else None
        self.parent = None if top is None else (top.name, top.start)
        self.ann = (jax.profiler.TraceAnnotation(
            self.name, **{k: v for k, v in self.attrs.items()
                          if isinstance(v, _SCALARS)})
            if self.annotate else _NULL_CONTEXT)
        self.ann.__enter__()
        self.start = time.monotonic_ns()
        rec._open.append(self)
        return self

    def __exit__(self, *exc):
        end = time.monotonic_ns()
        self.ann.__exit__(None, None, None)
        self.rec._open.pop()
        self.rec.rows.append((self.name, self.start, end, self.parent,
                              dict(self.attrs)))
        return False


class Spans:
    """In-memory span recorder; see the module docstring."""

    # a 30 s window at about 1k spans/s, with room to spare
    CAP = 2 ** 17

    def __init__(self, cap: int = CAP):
        self.rows: deque = deque(maxlen=cap)
        self.t0_ns: Optional[int] = None
        self._open: list = []

    def begin(self, t0_ns: int) -> None:
        """Note the serving clock's zero (``serve()``'s start)."""
        self.t0_ns = t0_ns

    def span(self, name: str, annotate: bool = True, **attrs):
        """Context manager recording one row for the time it is open."""
        return _Span(self, name, attrs, annotate)

    def note(self, **attrs) -> None:
        """Add attributes known only once the innermost span's work is
        done (they reach the row, not the profiler annotation)."""
        self._open[-1].attrs.update(attrs)

    @contextlib.contextmanager
    def gap(self):
        """Close every open span for the duration of the block and
        reopen each after it as a new row: the server wraps each
        ``yield`` in this, so the consumer's time is in no span."""
        frames = list(self._open)
        for f in reversed(frames):
            f.__exit__(None, None, None)
        try:
            yield
        finally:
            for f in frames:
                f.__enter__()


class _NullSpans:
    """Records nothing; every method is a no-op."""

    rows = ()

    def begin(self, t0_ns: int) -> None:
        pass

    def span(self, name: str, annotate: bool = True, **attrs):
        return _NULL_CONTEXT

    def note(self, **attrs) -> None:
        pass

    def gap(self):
        return _NULL_CONTEXT


NULL = _NullSpans()
