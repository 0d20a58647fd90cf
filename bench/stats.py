"""Arithmetic shared by the metric readers: percentiles and the staleness
of reads, on host-clock nanoseconds."""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it (``inf`` counts as a value)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])


def read_staleness_ns(read_due: np.ndarray, read_key: np.ndarray,
                      read_version: np.ndarray, write_at: np.ndarray,
                      write_key: np.ndarray,
                      write_version: np.ndarray) -> np.ndarray:
    """Staleness of each read: 0 when no write to its key newer than the
    one it returned was in place (``write_at``) before the read was due;
    otherwise the read's due instant minus the earliest such ``write_at``:
    the oldest write it missed.  ``write_at`` is a write's due instant or
    its acknowledgement on the host, whichever clock the caller holds the
    reads to.

    Versions order the writes of a key (the fill is the oldest, below
    every write); a read's version is that of the write it returned."""
    out = np.zeros(read_due.shape[0], np.int64)
    for k in np.unique(read_key):
        w = np.flatnonzero(write_key == k)
        w = w[np.argsort(write_version[w], kind="stable")]
        wv = write_version[w]
        # earliest instant among the writes from each position on
        first = np.minimum.accumulate(write_at[w][::-1])[::-1]
        first = np.append(first, np.iinfo(np.int64).max)
        r = np.flatnonzero(read_key == k)
        nxt = np.searchsorted(wv, read_version[r], side="right")
        missed = first[nxt]
        stale = missed < read_due[r]
        out[r[stale]] = read_due[r[stale]] - missed[stale]
    return out
