"""Requests answered correctly inside the window, over its length: the
answer's output reached the client before the window closed."""
import numpy as np


def read(run):
    h = run.hist
    n = h.issued
    ok = ((h.done_ns[:n] >= run.t0_ns) & (h.done_ns[:n] < run.t1_ns)
          & ~h.failed[:n])
    return float(np.sum(ok)) / run.seconds
