"""Replication bytes shipped per update served in the window
(``Cluster.replication_bytes`` over the updates answered), in MiB."""
import numpy as np

from bench.traffic import UPDATE


def read(run):
    n = run.hist.issued
    served = np.sum((run.sched.kind[:n] == UPDATE)
                    & (run.hist.done_ns[:n] >= 0) & ~run.hist.failed[:n])
    if not served or not run.counters["replication_bytes"]:
        return None
    return run.counters["replication_bytes"] / served / 2**20
