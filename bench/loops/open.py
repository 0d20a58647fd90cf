"""Open loop: each request is sent at its due instant whatever the server
does, from one thread.  Arrivals are a Poisson process conditioned on its
count (``arrivals: poisson``): the run's requests at instants drawn
uniformly over the window and sorted."""
import time

import numpy as np


def due_ns(traffic: dict, rng, n: int, seconds: float) -> np.ndarray:
    """The offsets from the window's start at which the requests are
    due."""
    if traffic.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrivals {traffic.get('arrivals')!r} "
                         f"for an open loop")
    return np.sort(rng.uniform(0.0, seconds * 1e9, n)).astype(np.int64)


def drive(client, t0_ns: int, t1_ns: int, traffic: dict) -> None:
    """Send every request at ``t0_ns + due``; return once all are sent."""
    hist, sched = client.hist, client.sched
    hist.due_ns[:] = t0_ns + sched.due_ns
    for i in range(len(sched)):
        delay = hist.due_ns[i] - time.perf_counter_ns()
        if delay > 0:
            time.sleep(delay / 1e9)
        client.send(i)
