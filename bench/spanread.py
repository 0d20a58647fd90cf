"""The program's own spans (``repro.analysis.spans``), as the per-layer
metrics read them.

The program records spans only while a profiler session is active, so
only a ``--trace 1`` run has them.  Each reader takes the records that
start inside the run's window ``[t0_ns, t1_ns)``; a per-request metric
takes the requests submitted before ``t1_ns - TAIL_NS``, since recording
stops with the profiler at ``t1_ns`` and the window's last requests would
otherwise lose their latest spans.  The recorder is imported when a
metric is read, never when a cell is loaded; where the program has no
recorder, or recorded nothing, every reader returns None.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

TAIL_NS = 1_000_000_000


def records(run) -> Optional[List]:
    """The records that start in the run's window, or None."""
    try:
        from repro.analysis import spans
    except ImportError:             # a program without the recorder
        return None
    recs = [r for r in spans.records() if run.t0_ns <= r.start < run.t1_ns]
    return recs or None


def served(run) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """(submit, dispatch start, answer) in ns of each request answered
    that was submitted in the window's cut and ran in a recorded
    ``enoki.dispatch``, or None."""
    recs = records(run)
    if recs is None:
        return None
    starts = {r.id: r.start for r in recs if r.name == "enoki.dispatch"}
    rows = [(r.start, starts[r.attrs["dispatch"]], r.end) for r in recs
            if r.name == "enoki.request" and not r.attrs["lost"]
            and r.start < run.t1_ns - TAIL_NS
            and r.attrs["dispatch"] in starts]
    if not rows:
        return None
    return tuple(np.asarray(c, np.int64) for c in zip(*rows))
