"""Plain sequential reference of YCSB core workload A on a replicated
keygroup of records of ``FIELDCOUNT`` fields: reads of whole records, and
updates that replace one field of a loaded record, never inserting.

It imports nothing of the system under test.  It restates the update
kind's field rule (``bench/kinds/field_update.py``: update ``u`` writes
field ``u % FIELDCOUNT`` with the first ``width // FIELDCOUNT`` floats of
its row) and the configuration's rules: one writer takes Lamport clocks
``c0+1 .. c0+n``; a record after a prefix of its key's updates, in intake
(ticket) order, is the fill with each of those updates' fields written in
turn; the last writer of a key wins.

The served run is held to it by these counts, each with the limit 0:

* ``lost``: requests due in the window that never completed, or failed;
* ``update_bad``: updates whose returned clock breaks the writer's
  sequence (exactly ``c0+1 .. c0+n``, no repeat, -1 for a write that found
  no record counting too), or falls along a key's intake order;
* ``read_unwritten``: reads that do not equal their key's record after
  some prefix of its updates in intake order (prefix 0 is the fill), or
  whose prefix holds an update sent after the read's answer arrived;
* ``read_regress``: reads older, by their prefix's clock, than a read of
  the same key that had completed before they were sent;
* ``arena_diff.<node>``: elements of that replica's drained arena (keys,
  values, lengths, versions, version vector) that differ from the fill
  with each key's fields overwritten in clock order and stamped with its
  last clock.

Per read, ``check`` returns its prefix's clock (the fill's for prefix 0),
per update the clock it took, as ``bench/reference.py`` does.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.reference import (LEAVES, Fill, Observed,  # noqa: F401
                             expected_arena, read_regressions)

FIELDCOUNT = 10


def field_of(update_id: int) -> int:
    return int(update_id) % FIELDCOUNT


def _write_field(record: np.ndarray, update_id: int, rows) -> None:
    ff = record.shape[0] // FIELDCOUNT
    f = field_of(update_id)
    record[f * ff:(f + 1) * ff] = rows(int(update_id))[:ff]


def check(obs: Observed, fill: Fill, rows, writer_id: int,
          arenas: Dict[str, dict]) -> Tuple[Dict[str, int], np.ndarray]:
    """Every count of the module docstring, and per request the Lamport
    clock of the record state it returned (a read) or took (an update), -1
    where unknown.  ``rows(u)`` is update u's row; ``arenas`` maps a
    replica's node to its leaves (host arrays)."""
    n = obs.kind.shape[0]
    ok = (obs.done_ns >= 0) & ~obs.failed
    out = {"lost": int(n - ok.sum())}
    width = fill.values.shape[1]
    if width % FIELDCOUNT:
        raise ValueError(f"{width} floats do not split into {FIELDCOUNT} "
                         f"fields")

    # ---- updates: one writer, one clock each, rising in intake order
    upd = np.flatnonzero((obs.kind == 1) & ok)
    upd = upd[np.argsort(obs.ticket[upd], kind="stable")]
    clock = np.full(n, -1, np.int64)
    clock[upd] = [int(np.asarray(obs.outputs[i])) for i in upd]
    want = np.arange(fill.clock + 1, fill.clock + 1 + upd.size)
    bad = int(np.sum(np.sort(clock[upd]) != want))
    by_key: Dict[int, np.ndarray] = {}
    for k in np.unique(obs.key[upd]):
        mine = upd[obs.key[upd] == k]
        by_key[int(k)] = mine
        bad += int(np.sum(np.diff(clock[mine]) <= 0))
    out["update_bad"] = bad

    # ---- reads: name the prefix of its key's updates each returned
    reads = np.flatnonzero((obs.kind == 0) & ok)
    unwritten = 0
    for k in np.unique(obs.key[reads]):
        k = int(k)
        mine = by_key.get(k, upd[:0])
        record = fill.values[fill.hot_slots[k]].astype(np.float32)
        prefix = {record.tobytes(): 0}
        for j, i in enumerate(mine, 1):
            _write_field(record, obs.update_id[i], rows)
            prefix.setdefault(record.tobytes(), j)
        sent = np.maximum.accumulate(obs.send_ns[mine]) if mine.size else []
        for i in reads[obs.key[reads] == k]:
            got = np.asarray(obs.outputs[i])
            j = (prefix.get(got.tobytes()) if got.dtype == np.float32
                 and got.shape == (width,) else None)
            if j is None or (j and sent[j - 1] > obs.done_ns[i]):
                unwritten += 1
                continue
            clock[i] = clock[mine[j - 1]] if j else fill.clock
    out["read_unwritten"] = unwritten
    out["read_regress"] = read_regressions(obs, reads, clock)

    # ---- the drained arenas: each key's fields in clock order
    last = {}
    for k, mine in by_key.items():
        record = fill.values[fill.hot_slots[k]].astype(np.float32)
        for i in mine[np.argsort(clock[mine], kind="stable")]:
            _write_field(record, obs.update_id[i], rows)
        last[k] = (int(clock[mine].max()), record)
    max_clock = int(clock[upd].max()) if upd.size else fill.clock
    want_arena = expected_arena(fill, writer_id, last, max_clock)
    for node, got in sorted(arenas.items()):
        diff = 0
        for leaf in LEAVES:
            a, b = np.asarray(got[leaf]), want_arena[leaf]
            if a.shape != b.shape:
                diff += max(a.size, b.size)
            else:
                diff += int(np.sum(a.astype(b.dtype) != b))
        out[f"arena_diff.{node}"] = diff
    return out, clock
