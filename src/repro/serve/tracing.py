"""Host spans of the serving program, kept in memory.

`span(name, **attrs)` times a block on `time.perf_counter_ns` and
records its name, start, end and parent (the span open around it on the
same thread), the engine and tick it belongs to and the request uid
where there is one (each inherited from the parent unless given), and
its counts (`attrs`).  `record` adds a span that is already over, such
as a request's wait in the queue.

Each span also enters `jax.profiler.TraceAnnotation(name)`: while a
profiler runs, the span lands in the same trace as the device's
operations, on its clock; with the profiler off that costs only the
check.  Finished spans go into one process-wide ring of `RING` spans
(the profiler too is one per process); `spans()` returns them.  Nothing
is written out while the engine ticks."""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

import jax

RING = 1 << 16


class Span(NamedTuple):
    id: int
    parent: int | None          # id of the enclosing span
    name: str
    start_ns: int
    end_ns: int
    engine: int | None
    tick: int | None
    uid: int | None
    attrs: dict


_ring: deque = deque(maxlen=RING)          # plain tuples in Span's order
_ids = itertools.count(1)
_engines = itertools.count()
_local = threading.local()
_Annotation = jax.profiler.TraceAnnotation


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def next_engine_id() -> int:
    return next(_engines)


class span:
    """`with span("engine.decode.dispatch", rows=3, batch=16):`"""

    __slots__ = ("name", "engine", "tick", "uid", "attrs", "id", "parent",
                 "start", "_ann")

    def __init__(self, name: str, *, engine: int | None = None,
                 tick: int | None = None, uid: int | None = None, **attrs):
        self.name, self.attrs = name, attrs
        self.engine, self.tick, self.uid = engine, tick, uid

    def __enter__(self) -> None:
        st = _stack()
        if st:
            up = st[-1]
            self.parent = up.id
            self.engine = up.engine if self.engine is None else self.engine
            self.tick = up.tick if self.tick is None else self.tick
            self.uid = up.uid if self.uid is None else self.uid
        else:
            self.parent = None
        self.id = next(_ids)
        st.append(self)
        self._ann = None
        if _Annotation.is_enabled():
            self._ann = _Annotation(self.name)
            self._ann.__enter__()
        self.start = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _stack().pop()
        _ring.append((self.id, self.parent, self.name, self.start, end,
                      self.engine, self.tick, self.uid, self.attrs))


def record(name: str, start_ns: int, end_ns: int, *, uid: int | None = None,
           **attrs) -> None:
    """A span that is already over, as a child of the span open now."""
    st = _stack()
    if st:
        up = st[-1]
        parent, engine, tick = up.id, up.engine, up.tick
        uid = up.uid if uid is None else uid
    else:
        parent = engine = tick = None
    _ring.append((next(_ids), parent, name, start_ns, end_ns, engine, tick,
                  uid, attrs))


def spans() -> list[Span]:
    """The finished spans in the ring, oldest first."""
    return [Span._make(t) for t in list(_ring)]

