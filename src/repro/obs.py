"""Program spans and counters: the project's one tracing facility.

``with span(name, **counts) as c:`` marks a host phase of the program.
While a ``jax.profiler`` session runs, it enters a
``jax.profiler.TraceAnnotation``, so the trace shows the phase on the
device ops' clock with its counts as stats (counts set on ``c`` inside the
block are added when it closes), and it appends the span to an in-memory
record that code in the same process reads after the session
(:func:`records`).  There is no other switch.  With no session, a span
costs one check and hands back its counts.  The module never imports JAX:
a process that has not, runs no session.  Spans nest on one thread.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List, NamedTuple, Optional

__all__ = ["MAX_RECORDS", "Span", "clear", "dropped", "profiling", "records", "span"]

#: spans kept per record; later ones are counted by :func:`dropped`
MAX_RECORDS = 100_000


class Span(NamedTuple):
    name: str
    parent: int  # index in the record of the enclosing span, -1 at the top
    start_ns: int  # time.perf_counter_ns()
    end_ns: int
    counts: Dict[str, int]


class _Record:
    def __init__(self) -> None:
        self.spans: List[Optional[Span]] = []  # None while a span is open
        self.stack: List[int] = []  # indices of the open spans
        self.dropped = 0
        self.generation = 0  # bumped by clear(), so spans open then are let go


_REC = _Record()


def profiling() -> bool:
    """True while a ``jax.profiler`` session is running in this process."""
    profiler = sys.modules.get("jax.profiler")
    return profiler is not None and profiler.TraceAnnotation.is_enabled()


class span:
    """A host phase of the program, with counts (see the module's docstring)."""

    __slots__ = ("name", "counts", "_given", "_me", "_index", "_gen", "_start")

    def __init__(self, name: str, **counts: int) -> None:
        self.name, self.counts = name, counts

    def __enter__(self) -> Dict[str, int]:
        self._me = None
        if not profiling():  # a TraceAnnotation made now would record nothing
            return self.counts
        self._me = sys.modules["jax.profiler"].TraceAnnotation(self.name, **self.counts)
        self._me.__enter__()
        self._given, self._index = dict(self.counts), None
        if len(_REC.spans) < MAX_RECORDS:
            self._index, self._gen = len(_REC.spans), _REC.generation
            _REC.spans.append(None)
            _REC.stack.append(self._index)
        else:
            _REC.dropped += 1
        self._start = time.perf_counter_ns()
        return self.counts

    def __exit__(self, *exc) -> None:
        if self._me is None:
            return
        if self._index is not None and self._gen == _REC.generation:
            end = time.perf_counter_ns()
            _REC.stack.pop()
            parent = _REC.stack[-1] if _REC.stack else -1
            _REC.spans[self._index] = Span(self.name, parent, self._start, end,
                                           dict(self.counts))
        added = {k: v for k, v in self.counts.items() if self._given.get(k) != v}
        if added:
            self._me.set_metadata(**added)
        self._me.__exit__(*exc)


def records() -> List[Optional[Span]]:
    """The spans recorded so far, in the order they opened."""
    return list(_REC.spans)


def dropped() -> int:
    """Spans left out of the record because it was full."""
    return _REC.dropped


def clear() -> None:
    """Empty the record and its count of dropped spans."""
    _REC.spans, _REC.stack, _REC.dropped = [], [], 0
    _REC.generation += 1
