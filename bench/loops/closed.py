"""Closed loop: ``clients`` requests outstanding at all times, with no
think time (YCSB's ``threadcount`` client model).  The window opens with
one request per client; each answer on the host frees its client, which
sends the next entry of the schedule, in schedule order, until the window
closes.  A request is due at the instant it is sent.

One client thread sends everything: the answers' callbacks, which run on
the server's threads, only hand the freed client over through a queue.
The schedule is a ceiling (the cell's ``rate_per_s`` times the window):
a run that uses it up before the window closes fails."""
import queue
import time

import numpy as np


def due_ns(traffic: dict, rng, n: int, seconds: float) -> np.ndarray:
    """No offsets: the loop stamps each request when it sends it."""
    return np.zeros(n, np.int64)


def drive(client, t0_ns: int, t1_ns: int, traffic: dict) -> None:
    """Keep ``clients`` requests outstanding from ``t0_ns`` until
    ``t1_ns``; return at ``t1_ns``."""
    n = len(client.sched)
    freed = queue.SimpleQueue()
    delay = t0_ns - time.perf_counter_ns()
    if delay > 0:
        time.sleep(delay / 1e9)
    sent = 0
    for _ in range(int(traffic["clients"])):
        sent = _send_next(client, sent, n, freed)
    while True:
        left = t1_ns - time.perf_counter_ns()
        if left <= 0:
            return
        try:
            freed.get(timeout=left / 1e9)
        except queue.Empty:
            return
        if time.perf_counter_ns() < t1_ns:
            sent = _send_next(client, sent, n, freed)


def _send_next(client, i: int, n: int, freed) -> int:
    if i >= n:
        raise RuntimeError(
            f"the closed loop used up its schedule of {n} requests before "
            f"the window closed: the cell's rate_per_s is below what the "
            f"clients reach")
    now = time.perf_counter_ns()
    client.send(i, due_ns=now, on_done=freed.put)
    return i + 1
