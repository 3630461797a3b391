"""Scheduler spans: what the serving engine's host loop was doing, and for
which request, kept in memory.

``Spans.on`` is the only switch and is off by default.  Off, ``span()``
hands back one shared object whose enter and exit do nothing, so a span
costs one attribute test.  On, each span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows it in the host plane on the device ops' clock, and appends a
:class:`Span` on ``time.perf_counter_ns``.

Span names the engine records (``scheduler.py``): ``sched.step`` (one
``step()``, the parent of the rest), ``sched.admit`` (one admission
attempt; ``n`` = the prompt tokens it admitted, 0 for an attempt that
found no pages; an encdec window counts its frames, and the prompt in
the last one; ``stalled`` when some slot was decoding as it began),
``sched.queue`` (memory only: submit or requeue to the start of the
admission that took the request), ``prefix.match`` (``n`` = prompt
tokens reused), ``prefix.insert``, ``sched.pages``, ``sched.decode``
(``n`` = active slots, ``runahead`` = steps in the burst) and
``sched.retire``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax


@dataclass(slots=True)
class Span:
    """One recorded span.  ``parent`` is the index in ``Spans.records`` of
    the span open around it (-1 for none), ``rid`` the request it serves
    (-1 for none), ``n`` one count whose meaning the name fixes."""
    name: str
    start_ns: int
    end_ns: int = 0
    parent: int = -1
    rid: int = -1
    n: int = 0
    runahead: int = 0
    stalled: bool = False


class _Off:
    """The shared stand-in while spans are off: enters, exits and takes
    attribute writes without doing anything."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __setattr__(self, name, value):
        pass


_OFF = _Off()


class _Open:
    """Context manager for one span while spans are on."""
    __slots__ = ("_spans", "_span", "_ann")

    def __init__(self, spans: "Spans", span: Span):
        self._spans, self._span = spans, span

    def __enter__(self) -> Span:
        self._ann = jax.profiler.TraceAnnotation(self._span.name)
        self._ann.__enter__()
        s = self._spans
        self._span.parent = s._stack[-1] if s._stack else -1
        s._stack.append(len(s.records))
        s.records.append(self._span)
        self._span.start_ns = time.perf_counter_ns()
        return self._span

    def __exit__(self, *exc):
        self._span.end_ns = time.perf_counter_ns()
        self._spans._stack.pop()
        self._ann.__exit__(*exc)
        return False


class Spans:
    """In-memory span recorder; see the module docstring."""

    def __init__(self):
        self.on = False
        self.records: list[Span] = []
        self._stack: list[int] = []

    def span(self, name: str, rid: int = -1, n: int = 0, runahead: int = 0,
             stalled: bool = False):
        """A context manager around one span; ``as`` gives the
        :class:`Span` (or, while off, a stand-in that ignores writes), so
        a count known only at the end can be set inside."""
        if not self.on:
            return _OFF
        return _Open(self, Span(name, 0, rid=rid, n=n, runahead=runahead,
                                stalled=stalled))

    def record(self, name: str, start_ns: int, end_ns: int, rid: int = -1,
               n: int = 0) -> None:
        """Add a span that began in the past (memory only: the profiler
        has no place for it).  Its parent is the span open now."""
        if self.on:
            self.records.append(Span(
                name, start_ns, end_ns,
                parent=self._stack[-1] if self._stack else -1, rid=rid, n=n))
