"""Plain sequential reference of a YCSB keygroup, and the comparison that
decides ``correct``.

It imports nothing of the system under test.  It knows the records the
benchmark filled (keys, values, the fill's writer and clock) and the
requests the client sent, and holds one replica as plain arrays with the
configuration's rules: a write takes the next Lamport clock of its node,
``clock = max(clock, highest clock in the version vector) + 1``, stamps
the packed version ``clock * 64 + node id``, and the last writer of a key
wins.  Every replica of a drained keygroup holds the same contents, so one
expected arena serves for all of them.

The served run is held to it by these counts, each with the limit 0:

* ``lost``: requests due in the window that never completed, or failed;
* ``update_bad``: updates whose returned clock breaks the writer's
  sequence (the clocks of all updates are exactly ``c0+1 .. c0+n``, with
  no repeat, and rise along each key's issue order);
* ``read_unwritten``: reads that returned a row that is neither the key's
  filled record nor an update of that key issued before the read's output
  reached the client;
* ``read_regress``: reads older, by version, than a read of the same key
  that had completed before they were sent (a replica only moves forward);
* ``arena_diff.<node>``: elements of that replica's arena (keys, values,
  lengths, versions, version vector) that differ from the reference's.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

NODES_PACKED = 64          # version = clock * 64 + node id
LEAVES = ("keys", "values", "lengths", "versions", "vv")


def fnv1a(key: str) -> int:
    """31-bit FNV-1a of a key name, 0 reserved for an empty slot."""
    h = 0x811C9DC5
    for ch in key.encode("utf-8"):
        h ^= ch
        h = (h * 0x01000193) & 0xFFFFFFFF
    h &= 0x7FFFFFFF
    return h if h != 0 else 1


def pack(clock, node_id):
    return np.asarray(clock, np.int64) * NODES_PACKED + node_id


@dataclasses.dataclass
class Fill:
    """The records as the benchmark filled them into every replica."""
    keys: np.ndarray            # (S,) int32
    values: np.ndarray          # (S, V) float32
    hot_slots: np.ndarray       # slot of request key i
    writer_id: int              # node id the fill is stamped with
    clock: int = 1              # the fill's Lamport clock


@dataclasses.dataclass
class Observed:
    """What the client saw, one entry per request of the window."""
    kind: np.ndarray            # 0 read, 1 update
    key: np.ndarray             # request key index
    update_id: np.ndarray       # which update row (-1 for reads)
    ticket: np.ndarray          # the server's ticket (its intake order)
    send_ns: np.ndarray
    done_ns: np.ndarray         # -1: never completed
    failed: np.ndarray          # bool: completed with an error
    outputs: List[object]       # read: row; update: clock


def expected_arena(fill: Fill, writer_id: int,
                   last: Dict[int, tuple], max_clock: int) -> dict:
    """The drained replica: the fill with each written key's last row.
    ``last`` maps key index -> (clock, row)."""
    values = fill.values.copy()
    versions = np.full(fill.keys.shape, pack(fill.clock, fill.writer_id),
                       np.int64)
    for k, (clock, row) in last.items():
        slot = fill.hot_slots[k]
        values[slot] = row
        versions[slot] = pack(clock, writer_id)
    vv = np.zeros(NODES_PACKED, np.int64)
    vv[fill.writer_id] = fill.clock
    vv[writer_id] = max(vv[writer_id], max_clock)
    return {"keys": fill.keys, "values": values,
            "lengths": np.full(fill.keys.shape, fill.values.shape[1]),
            "versions": versions, "vv": vv}


def check(obs: Observed, fill: Fill, rows, writer_id: int,
          arenas: Dict[str, dict]) -> Tuple[Dict[str, int], np.ndarray]:
    """Every count of the module docstring, and per request the Lamport
    clock of the write it returned (a read) or took (an update), -1 where
    unknown.  ``rows(u)`` is update u's record; ``arenas`` maps a
    replica's node to its leaves (host arrays)."""
    n = obs.kind.shape[0]
    done = obs.done_ns >= 0
    ok = done & ~obs.failed
    out = {"lost": int(n - ok.sum())}

    # ---- updates: one writer, one clock each, in each key's intake order
    upd = np.flatnonzero((obs.kind == 1) & ok)
    clocks = np.array([int(np.asarray(obs.outputs[i])) for i in upd],
                      np.int64)
    want = np.arange(fill.clock + 1, fill.clock + 1 + upd.size)
    bad = int(np.sum(np.sort(clocks) != want))
    clock_of = {}                       # update id -> its clock
    for i, c in zip(upd, clocks):
        clock_of[int(obs.update_id[i])] = int(c)
    for k in np.unique(obs.key[upd]):
        mine = upd[obs.key[upd] == k]
        order = np.argsort(obs.ticket[mine], kind="stable")
        seq = np.array([clock_of[int(obs.update_id[i])]
                        for i in mine[order]])
        bad += int(np.sum(np.diff(seq) <= 0))
    out["update_bad"] = bad

    # ---- reads: name the write each returned, then order them
    by_row = {rows(int(u)).tobytes(): int(u)
              for u in obs.update_id[obs.kind == 1]}
    upd_key = {int(obs.update_id[i]): int(obs.key[i])
               for i in np.flatnonzero(obs.kind == 1)}
    upd_send = {int(obs.update_id[i]): int(obs.send_ns[i])
                for i in np.flatnonzero(obs.kind == 1)}
    reads = np.flatnonzero((obs.kind == 0) & ok)
    read_clock = np.full(n, -1, np.int64)
    read_clock[upd] = clocks
    unwritten = 0
    for i in reads:
        got = np.asarray(obs.outputs[i])
        k = int(obs.key[i])
        if (got.dtype == fill.values.dtype
                and np.array_equal(got, fill.values[fill.hot_slots[k]])):
            read_clock[i] = fill.clock
            continue
        u = by_row.get(got.tobytes()) if got.dtype == np.float32 else None
        if (u is None or upd_key[u] != k or u not in clock_of
                or upd_send[u] > obs.done_ns[i]):
            unwritten += 1
            continue
        read_clock[i] = clock_of[u]
    out["read_unwritten"] = unwritten
    out["read_regress"] = read_regressions(obs, reads, read_clock)

    # ---- the drained arenas, leaf by leaf
    last = {}
    for u, c in clock_of.items():
        k = upd_key[u]
        if k not in last or c > last[k][0]:
            last[k] = (c, rows(u))
    max_clock = max(clock_of.values(), default=fill.clock)
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
    return out, read_clock


def read_regressions(obs: Observed, reads: np.ndarray,
                     read_clock: np.ndarray) -> int:
    """Reads that returned an older version of their key than some read
    of the same key that had completed before they were sent."""
    bad = 0
    for k in np.unique(obs.key[reads]):
        mine = reads[(obs.key[reads] == k) & (read_clock[reads] >= 0)]
        by_done = mine[np.argsort(obs.done_ns[mine], kind="stable")]
        done_sorted = obs.done_ns[by_done]
        best = np.maximum.accumulate(read_clock[by_done])
        for i in mine:
            j = np.searchsorted(done_sorted, obs.send_ns[i], side="left")
            if j > 0 and best[j - 1] > read_clock[i]:
                bad += 1
    return bad
