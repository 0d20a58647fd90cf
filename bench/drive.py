"""The client: offers a schedule to ``FaasServer.submit`` from one process
and records, on the host clock, when each request was due, sent and
answered.

The open loop sends each request at its due instant whatever the server
does, from one thread.  A request's answer counts when its output is on
the host: the future's completion callback stamps it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np

from bench.traffic import READ, Schedule

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


def request(dep, sched: Schedule, i: int):
    """(function, input) of request ``i``."""
    k = int(sched.key[i])
    if sched.kind[i] == READ:
        return dep.read_fn, np.float32([k])
    return dep.update_fns[k], sched.row(int(sched.update_id[i]))


def _send(srv, dep, sched: Schedule, hist: History, i: int):
    fn, x = request(dep, sched, i)
    hist.send_ns[i] = time.perf_counter_ns()
    fut = srv.submit(fn, x)
    hist.ticket[i] = fut.ticket
    hist.issued = max(hist.issued, i + 1)
    fut.add_done_callback(lambda f, i=i: hist.answer(i, f))
    return fut


def open_loop(srv, dep, sched: Schedule, t0_ns: int) -> History:
    """Send every request at ``t0_ns + due``; return once all are sent."""
    hist = History.empty(len(sched))
    hist.due_ns[:] = t0_ns + sched.due_ns
    for i in range(len(sched)):
        delay = hist.due_ns[i] - time.perf_counter_ns()
        if delay > 0:
            time.sleep(delay / 1e9)
        _send(srv, dep, sched, hist, i)
    return hist


def wait_answers(hist: History, t1_ns: int,
                 wait_s: float = WAIT_AFTER_S) -> None:
    """Wait until every issued request is answered, or ``wait_s`` past the
    window's close."""
    deadline = t1_ns + int(wait_s * 1e9)
    while time.perf_counter_ns() < deadline:
        if np.all(hist.done_ns[:hist.issued] >= 0):
            return
        time.sleep(0.01)
