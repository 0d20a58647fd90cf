"""95th percentile of the staleness of every read due in the window
(``bench.stats.read_staleness_ns``): 0 for a read that returned the newest
write to its key due before the read was due, else the read's due instant
minus that of the oldest write it missed.  Host clock; the write a read
returned is named by its row, each update's row being unique.  The same
percentile with writes counted from their acknowledgement goes to
standard error beside it."""
import sys

import numpy as np

from bench.stats import percentile, read_staleness_ns
from bench.traffic import READ, UPDATE


def read(run):
    idx = run.attempted()
    kind = run.sched.kind[idx]
    reads, writes = idx[kind == READ], idx[kind == UPDATE]
    if reads.size == 0 or writes.size == 0:
        return None
    h, key, clk = run.hist, run.sched.key, run.clocks
    acked = np.where(h.done_ns >= 0, h.done_ns, np.iinfo(np.int64).max)
    by = {}
    for name, at in (("due", h.due_ns), ("ack", acked)):
        stale = read_staleness_ns(h.due_ns[reads], key[reads], clk[reads],
                                  at[writes], key[writes], clk[writes])
        by[name] = (percentile(stale / 1e6, 95), float((stale > 0).mean()))
    print(f"staleness p95 ms / stale share: from due {by['due'][0]} / "
          f"{by['due'][1]}, from acknowledgement {by['ack'][0]} / "
          f"{by['ack'][1]}", file=sys.stderr, flush=True)
    return by["due"][0]
