"""Spans and counters of the served path, kept in memory.

``span(name, **attrs)`` times a block of host work.  Each closed span is
one ``Span`` record in a bounded ring: its id, its parent's id, the request
id it serves, its name, the thread it ran on and its wall interval
``t0..t1`` on ``time.monotonic()`` (the clock the benchmark aligns its
device trace to).  A span opened with ``cpu=True`` also holds the thread's
CPU time ``c0..c1`` (``time.thread_time()``), so the wall time between two
such spans splits into time the thread ran and time it could not.  Only
some spans read it: on a TPU VM host one read costs several microseconds
and the clock advances in 10 ms ticks, so it is read where many intervals
are averaged (the decode loop's waits), not on every span.
While a profiler session is recording, the span is also a
``jax.profiler.TraceAnnotation``, so an xprof or Perfetto trace shows it
beside the device ops.

A span's parent is the innermost open span on its thread.  Work handed to
another thread names its parent there with ``handoff(parent)``; spans of
one request share the request id of the span that started it.

``count(name, n)`` adds to a counter.  ``snapshot()`` (the spans and
how many the ring dropped), ``spans()`` and ``counters()`` are for
readers: the benchmark, and ``proxy.stats()["telemetry"]``.  Backend compiles are counted (``compiles``) and recorded as
``jax.compile`` spans that end when JAX reports them, so a compile inside
a measured window names itself as the cause of the time it took.

Recording is always on and process-wide, like the profiler it feeds.  The
ring holds ``RING`` spans, enough for a saturated stream cell's 51 s
window (about 150 tokens a second at about ten spans a token), and drops
the oldest beyond that.  The hot
path takes no lock: appending to the ring and drawing ids are atomic.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

RING = 1 << 17


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    rid: Optional[str]
    name: str
    thread: int
    t0: float            # time.monotonic()
    t1: float
    c0: Optional[float]  # time.thread_time() of the span's thread,
    c1: Optional[float]  # where it was opened with cpu=True
    attrs: Optional[Dict[str, Any]]


# closed spans as plain tuples (a Span and its close number), made into
# ``Span``s only when read
_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_closes = itertools.count()
_tl = threading.local()
_counters: Dict[str, int] = {}
_counter_lock = threading.Lock()

_annotation = jax.profiler.TraceAnnotation
_enabled = _annotation.is_enabled
_monotonic, _thread_time, _ident = (time.monotonic, time.thread_time,
                                    threading.get_ident)


def _stack() -> list:
    try:
        return _tl.stack
    except AttributeError:
        _tl.stack = []
        return _tl.stack


def _pop(st: list, frame) -> None:
    if st and st[-1] is frame:
        st.pop()
    elif frame in st:            # closed out of order: never leak a frame
        st.remove(frame)


class _Frame(NamedTuple):
    """A parent named on a thread it did not open on (``handoff``)."""
    id: Optional[int]
    rid: Optional[str]


class _Open:
    """One open span; ``t0``/``t1`` hold its interval once it closed."""
    __slots__ = ("name", "rid", "cpu", "attrs", "id", "parent", "t0", "t1",
                 "c0", "_tm")

    def __init__(self, name: str, rid: Optional[str], cpu: bool, attrs):
        self.name = name
        self.rid = rid
        self.cpu = cpu
        self.attrs = attrs or None

    def __enter__(self) -> "_Open":
        st = _stack()
        if st:
            top = st[-1]
            self.parent = top.id
            if self.rid is None:
                self.rid = top.rid
        else:
            self.parent = None
        self.id = next(_ids)
        st.append(self)
        if _enabled():
            self._tm = _annotation(self.name)
            self._tm.__enter__()
        else:
            self._tm = None
        self.c0 = _thread_time() if self.cpu else None
        self.t0 = _monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = t1 = _monotonic()
        c1 = _thread_time() if self.cpu else None
        if self._tm is not None:
            self._tm.__exit__(*exc)
        _pop(_stack(), self)
        _ring.append((self.id, self.parent, self.rid, self.name, _ident(),
                      self.t0, t1, self.c0, c1, self.attrs, next(_closes)))
        return False


def span(name: str, rid: Optional[str] = None, cpu: bool = False,
         **attrs) -> _Open:
    """A context manager timing the block as span ``name``; ``rid`` names
    the request where no enclosing span carries it; ``cpu`` reads the
    thread's CPU time at both ends too."""
    return _Open(name, rid, cpu, attrs)


def current():
    """The innermost open span on this thread, or None: what a thread that
    hands work on passes to ``handoff``."""
    st = _stack()
    return st[-1] if st else None


class handoff:
    """Open ``parent`` (a span of another thread, or None) as this thread's
    outermost frame, so spans opened here are its children and serve its
    request (``rid`` where it has none)."""

    def __init__(self, parent, rid: Optional[str] = None):
        self.frame = _Frame(parent.id if parent is not None else None,
                            (parent.rid if parent is not None
                             and parent.rid is not None else rid))

    def __enter__(self) -> _Frame:
        _stack().append(self.frame)
        return self.frame

    def __exit__(self, *exc) -> bool:
        _pop(_stack(), self.frame)
        return False


def count(name: str, n: int = 1) -> None:
    with _counter_lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> Dict[str, int]:
    with _counter_lock:
        return dict(_counters)


def snapshot():
    """``(spans, dropped)``: every span the ring holds, in the order they
    closed, and how many spans closed since the process started that the
    ring let go."""
    recs = list(_ring)
    closed = max((r[-1] for r in recs), default=-1) + 1
    return [Span._make(r[:-1]) for r in recs], closed - len(recs)


def spans() -> List[Span]:
    return snapshot()[0]


def _on_duration(event: str, duration: float, **kw) -> None:
    if "backend_compile" not in event:
        return
    t1 = _monotonic()
    top = current()
    count("compiles")
    _ring.append((next(_ids), top.id if top is not None else None,
                  top.rid if top is not None else None, "jax.compile",
                  _ident(), t1 - duration, t1, None, None, None,
                  next(_closes)))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
