"""Span log of the serving path.

``span(name, **ids)`` times one stage of serving on the host::

    with span("serve.plan", wave=7) as ids:
        ...
        ids["members"] = 16  # ids may be added before the span closes

Each span is recorded, when it closes, as a :class:`Span`: its name, its
start and end on ``time.monotonic_ns()`` (the clock of ``time.monotonic``,
so a reader can clip the log to an interval it measured itself), the
``sid`` of the span enclosing it on the same thread (``parent``; each
thread keeps its own stack) and its ids (``rid=`` for the spans of one
request, ``wave=`` for those of one wave).

Records go into one fixed-capacity ring, :data:`LOG`: once it holds
``CAPACITY`` records the oldest are dropped and counted, and
:func:`window` refuses an interval that dropped records reach into.

Each span also enters ``jax.profiler.TraceAnnotation(name)``: under the
profiler it lands on the host plane of the trace, on the clock of the
device's operations.  Recording is always on and costs a few
microseconds a span, so spans sit only at stage boundaries, never inside
per-tile, per-leaf or per-edge loops.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["CAPACITY", "LOG", "Span", "SpanLog", "self_ns", "span", "window"]

#: records the ring holds: a 20 s window of molecule screening, with its
#: warm-up, records about 20k
CAPACITY = 1 << 16


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]  # sid of the enclosing span on the same thread
    ids: dict
    sid: int  # unique in the process

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


class SpanLog:
    """Bounded, thread-safe ring of closed spans."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: deque[Span] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0
        self._dropped_end: Optional[int] = None  # latest end of a dropped span

    def add(self, rec: Span) -> None:
        with self._lock:
            ring = self._ring
            if len(ring) == ring.maxlen:
                old = ring[0]
                self.dropped += 1
                if self._dropped_end is None or old.end_ns > self._dropped_end:
                    self._dropped_end = old.end_ns
            ring.append(rec)

    def records(self) -> list[Span]:
        with self._lock:
            return list(self._ring)

    def window(self, t0_ns: int, t1_ns: int) -> Optional[list[Span]]:
        """Spans that start inside ``[t0_ns, t1_ns]``, in order of start;
        ``None`` if a dropped span reached into the interval."""
        with self._lock:
            if self._dropped_end is not None and self._dropped_end >= t0_ns:
                return None
            recs = [r for r in self._ring if t0_ns <= r.start_ns <= t1_ns]
        recs.sort(key=lambda r: r.start_ns)
        return recs


#: the process's span log
LOG = SpanLog()

_local = threading.local()
_next_sid = itertools.count(1).__next__


class span:
    """Context manager timing one stage; ``as`` gives its ids dict."""

    __slots__ = ("name", "ids", "sid", "parent", "_start", "_note")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids

    def __enter__(self) -> dict:
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else None
        self.sid = _next_sid()
        stack.append(self.sid)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        self._start = time.monotonic_ns()
        return self.ids

    def __exit__(self, *exc) -> bool:
        end = time.monotonic_ns()
        self._note.__exit__(*exc)
        _local.stack.pop()
        LOG.add(Span(self.name, self._start, end, self.parent, self.ids, self.sid))
        return False


def window(t0_ns: int, t1_ns: int) -> Optional[list[Span]]:
    """Spans of the process log that start inside ``[t0_ns, t1_ns]``
    (``time.monotonic_ns()``), or ``None`` if dropped spans reach into it."""
    return LOG.window(t0_ns, t1_ns)


def self_ns(recs: list[Span]) -> dict[int, int]:
    """Each span's own time: its duration less the part of it that its
    children among ``recs`` cover (sid -> ns)."""
    kids: dict[int, list[tuple[int, int]]] = {}
    for r in recs:
        if r.parent is not None:
            kids.setdefault(r.parent, []).append((r.start_ns, r.end_ns))
    out = {}
    for r in recs:
        covered, t = 0, r.start_ns
        for s, e in sorted(kids.get(r.sid, ())):
            s, e = max(s, t), min(e, r.end_ns)
            if e > s:
                covered += e - s
                t = e
        out[r.sid] = r.ns - covered
    return out
