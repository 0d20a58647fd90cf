"""Spans and counts of the served path, on the profiler's clock.

The recorder is on exactly while a JAX profiler session is active
(``jax.profiler.TraceAnnotation.is_enabled()``: true between
``jax.profiler.start_trace`` and ``stop_trace``).  There is no flag: an
operator who captures a profile gets the spans; with no session every
site costs one ``is_enabled()`` check, and nothing is built before it.

A thread-scoped span is written twice: as a ``TraceAnnotation`` under its
static name, so it lies on the host plane of the device trace (no
keyword arguments: they would go into the event's name), and as a
``Record`` in memory, timed with ``time.perf_counter_ns()`` (the clock the
benchmark's client and window use).  ``enoki.request`` crosses threads
(submitted on the client's, resolved on the serving loop's) and is
written to memory only.  The counts ride on the spans as attributes,
recorded where the work happens.

| Span | Where | Attributes |
|---|---|---|
| ``enoki.request`` | ``FaasServer.submit`` entry to ``_resolve``/``_fail`` | ``cycle``, ``dispatch`` (ids of the spans that served it, 0 if none), ``lost`` |
| ``enoki.submit`` | ``FaasServer.submit`` on the client's thread | the ``ticket`` field |
| ``enoki.turn`` | one pump turn of the serving loop (pump, deliver, fail lost) | ``delivered``: futures resolved during the turn |
| ``enoki.sleep`` | the serving loop's wait for the next deadline or submit | |
| ``enoki.cycle`` | ``BatchedInvocationEngine._run_cycle`` | ``windows``, ``requests`` |
| ``enoki.dispatch`` | one chunk of ``BatchedInvocationEngine._exec_group`` (``_exec_chunk``) | ``fn``, ``node``, ``kind`` (``scan`` or ``map``), ``n``, ``bucket``, ``keyed_gets`` / ``keyed_sets`` (the handler's ops by a key the request carries, per request) |
| ``enoki.stage`` | host staging of a chunk's inputs (child of dispatch) | |
| ``enoki.call`` | the jitted handler call (child of dispatch) | |
| ``enoki.fetch`` | ``jax.device_get`` of the chunk's outputs (child of dispatch) | |
| ``enoki.merge`` | one fused replica merge in ``Cluster._deliver_until`` | ``k``, ``k_padded``, ``seqs`` (the snapshots merged) |
| ``enoki.replicate`` | ``Cluster._schedule_replication`` (the ``arena_clone``) | ``bytes``, ``targets``, ``seqs`` (one per target) |

A span's parent is the innermost span open on its thread, or the one
named where it starts (a dispatch on a parallel-pump lane names its
cycle).  Records sit in one bounded buffer per process; when it is full
the oldest go and ``dropped()`` counts them.  They leave it only when
asked for (``records()``), never on the served path.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

from repro.analysis import lockdep

REQUEST = "enoki.request"
SUBMIT = "enoki.submit"
TURN = "enoki.turn"
SLEEP = "enoki.sleep"
CYCLE = "enoki.cycle"
DISPATCH = "enoki.dispatch"
STAGE = "enoki.stage"
CALL = "enoki.call"
FETCH = "enoki.fetch"
MERGE = "enoki.merge"
REPLICATE = "enoki.replicate"

CAPACITY = 1 << 20              # records kept; the oldest go first

_Annotation = jax.profiler.TraceAnnotation
on = _Annotation.is_enabled     # the one check every site makes


class Record(NamedTuple):
    name: str
    start: int                  # time.perf_counter_ns()
    end: int
    thread: int                 # threading.get_ident() where it ended
    id: int
    parent: int                 # 0: none
    ticket: int                 # -1: none
    attrs: Optional[Dict[str, Any]]


class Recorder:
    """The bounded record buffer, and which cycle and dispatch served each
    engine ticket (until the ticket's request span ends).  Records are
    kept as plain tuples and become ``Record``s when read."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._lock = lockdep.make_lock("spans.lock")
        self._buf: "collections.deque[tuple]" = collections.deque(
            maxlen=capacity)
        self._dropped = 0
        self._served: Dict[tuple, tuple] = {}

    def add(self, rec: tuple) -> None:
        with self._lock:
            if len(self._buf) == self.capacity:
                self._dropped += 1
            self._buf.append(rec)

    def serve(self, owner: int, tickets, cycle: int, dispatch: int) -> None:
        with self._lock:
            for t in tickets:
                self._served[(owner, t)] = (cycle, dispatch)
            while len(self._served) > self.capacity:
                del self._served[next(iter(self._served))]

    def served_by(self, owner: int, ticket: int) -> tuple:
        with self._lock:
            return self._served.pop((owner, ticket), (0, 0))

    def records(self) -> List[Record]:
        with self._lock:
            buf = list(self._buf)
        return [Record._make(r) for r in buf]

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0
            self._served.clear()


# one per process, as the profiler session it follows is
RECORDER = Recorder()
records = RECORDER.records
dropped = RECORDER.dropped
clear = RECORDER.clear

_ids = itertools.count(1)
_tls = threading.local()
_now = time.perf_counter_ns
_thread = threading.get_ident


class Span:
    """An open thread-scoped span; ``end`` it on the thread that began
    it."""
    __slots__ = ("name", "start_ns", "id", "parent", "ticket", "attrs",
                 "_ann", "_stack")

    def __init__(self, name: str, parent: int):
        try:
            st = _tls.stack
        except AttributeError:
            st = _tls.stack = []
        self.name = name
        self.id = next(_ids)
        self.parent = parent or (st[-1].id if st else 0)
        self.ticket = -1
        self.attrs: Optional[Dict[str, Any]] = None
        st.append(self)
        self._stack = st
        self._ann = _Annotation(name)
        self._ann.__enter__()
        self.start_ns = _now()

    def set(self, **attrs) -> None:
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def end(self, **attrs) -> None:
        end_ns = _now()
        self._ann.__exit__(None, None, None)
        st = self._stack
        if st and st[-1] is self:
            st.pop()
        elif self in st:
            # spans above this one that an exception left open go with it
            del st[st.index(self):]
        if self.attrs is not None:
            self.attrs.update(attrs)
            attrs = self.attrs
        RECORDER.add((self.name, self.start_ns, end_ns, _thread(), self.id,
                      self.parent, self.ticket, attrs or None))


def start(name: str, parent: int = 0) -> Optional[Span]:
    """Begin span ``name`` (a static name from the table above), or
    return None when no profiler session is active.  ``parent`` names the
    causing span where it is not open on this thread."""
    if not on():
        return None
    return Span(name, parent)


def serve(owner, tickets, cycle: int, dispatch: int) -> None:
    """Mark ``tickets`` of engine ``owner`` as served by ``dispatch``, a
    span of ``cycle``: their request spans carry both ids."""
    RECORDER.serve(id(owner), tickets, cycle, dispatch)


class Request:
    """The memory-only span of one served request: from the submit's
    entry (``sub``, the ``enoki.submit`` span) to its future's
    resolution, on whichever thread resolves it."""
    __slots__ = ("start_ns", "id", "ticket", "owner")

    def __init__(self, sub: Span, ticket: int, owner):
        self.start_ns = sub.start_ns
        self.id = next(_ids)
        self.ticket = ticket
        self.owner = id(owner)

    def end(self, lost: bool) -> None:
        cycle, dispatch = RECORDER.served_by(self.owner, self.ticket)
        RECORDER.add((REQUEST, self.start_ns, _now(), _thread(), self.id, 0,
                      self.ticket, {"cycle": cycle, "dispatch": dispatch,
                                    "lost": int(lost)}))
