"""The client: offers a schedule to ``FaasServer.submit`` from one process
and records, on the host clock, when each request was due, sent and
answered.

A client loop (``bench/loops/<loop>.py``) decides when each request is
sent.  A request's answer counts when its output is on the host: the
future's completion callback stamps it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

from bench.traffic import Schedule

WAIT_AFTER_S = 60.0             # how long past the window answers may come


@dataclasses.dataclass
class History:
    """Per request of the schedule (``issued`` of them were sent)."""
    due_ns: np.ndarray
    send_ns: np.ndarray
    done_ns: np.ndarray             # -1 until answered
    failed: np.ndarray
    ticket: np.ndarray
    outputs: List[object]
    issued: int = 0

    @classmethod
    def empty(cls, n: int) -> "History":
        return cls(due_ns=np.full(n, -1, np.int64),
                   send_ns=np.full(n, -1, np.int64),
                   done_ns=np.full(n, -1, np.int64),
                   failed=np.zeros(n, bool), ticket=np.full(n, -1, np.int64),
                   outputs=[None] * n)

    def answer(self, i: int, fut) -> None:
        """Future callback: stamp the answer of request ``i``."""
        now = time.perf_counter_ns()
        try:
            self.outputs[i] = fut.result().output
        except Exception as e:          # lost or failed: counted, not raised
            self.outputs[i] = e
            self.failed[i] = True
        self.done_ns[i] = now


def route(dep, sched: Schedule) -> np.ndarray:
    """Per schedule entry, the index in ``dep.functions`` of the kind that
    serves it; an entry that no kind or two kinds serve is an error."""
    owner = np.full(len(sched), -1, np.int64)
    for j, (kind, entry) in enumerate(dep.functions):
        mine = np.asarray(kind.serves(dep, entry, sched.kind, sched.key))
        if np.any(owner[mine] >= 0):
            raise ValueError(f"function {entry['name']!r} serves requests "
                             f"another function serves")
        owner[mine] = j
    if np.any(owner < 0):
        i = int(np.argmax(owner < 0))
        raise ValueError(f"no function serves request {i} (kind "
                         f"{int(sched.kind[i])}, key {int(sched.key[i])})")
    return owner


class Client:
    """Sends entries of a schedule to the server and records them in its
    ``hist``; a client loop (``bench/loops/<loop>.py``) decides when."""

    def __init__(self, srv, dep, sched: Schedule):
        self.srv, self.dep, self.sched = srv, dep, sched
        self.owner = route(dep, sched)
        self.hist = History.empty(len(sched))

    def request(self, i: int):
        """(function, input) of request ``i``."""
        kind, entry = self.dep.functions[self.owner[i]]
        return kind.request(self.dep, entry, self.sched, i)

    def send(self, i: int, due_ns: Optional[int] = None,
             on_done: Optional[Callable[[int], None]] = None):
        """Submit request ``i`` (due at ``due_ns`` when given); once its
        answer is stamped, ``on_done(i)``."""
        hist = self.hist
        fn, x = self.request(i)
        if due_ns is not None:
            hist.due_ns[i] = due_ns
        hist.send_ns[i] = time.perf_counter_ns()
        fut = self.srv.submit(fn, x)
        hist.ticket[i] = fut.ticket
        hist.issued = max(hist.issued, i + 1)

        def answered(f, i=i):
            hist.answer(i, f)
            if on_done is not None:
                on_done(i)
        fut.add_done_callback(answered)
        return fut


def wait_answers(hist: History, t1_ns: int,
                 wait_s: float = WAIT_AFTER_S) -> None:
    """Wait until every issued request is answered, or ``wait_s`` past the
    window's close."""
    deadline = t1_ns + int(wait_s * 1e9)
    while time.perf_counter_ns() < deadline:
        if np.all(hist.done_ns[:hist.issued] >= 0):
            return
        time.sleep(0.01)
