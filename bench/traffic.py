"""The one traffic generator: turns a mix file (``bench/traffic/<name>.json``)
and a seed into the requests of a run.

Every seed gives the same SET of work in another order.  The work comes in
blocks of ``block`` operations, each block's (kind, key) multiset from the
mix's key distribution (``bench/dists/<requestdistribution>.py``,
``uniform`` where the mix names none), which draws it from streams that
the seed does not touch.  The seed only shuffles each block and places
the arrivals, which the mix's client loop (``bench/loops/<loop>.py``)
makes: the open loop's are a Poisson process conditioned on its count,
``rate x seconds`` instants drawn uniformly over the window and sorted, so
every seed offers the same number of requests.  Update rows are drawn from
the seed and are all distinct, so a value read back names the write that
produced it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from bench import spec

READ, UPDATE = 0, 1
ROWS_PER_CHUNK = 1024


def apportion(total: int, shares: np.ndarray) -> np.ndarray:
    """Whole counts summing to ``total`` in proportion to ``shares``
    (largest remainder; ties to the lower index)."""
    exact = np.asarray(shares, np.float64) * total
    counts = np.floor(exact).astype(np.int64)
    short = total - int(counts.sum())
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def distribution(traffic: dict, root=spec.ROOT):
    """The mix's key distribution module."""
    return spec.load_part("dists", traffic.get("requestdistribution",
                                               "uniform"), root)


def block_ops(traffic: dict, keys: int, index: int = 0,
              root=spec.ROOT) -> np.ndarray:
    """The (kind, key) multiset of block ``index``, unshuffled:
    (block, 2)."""
    return distribution(traffic, root).block(traffic, keys, index)


@dataclasses.dataclass
class Schedule:
    """The requests of one run, in issue order.

    ``kind``/``key`` per request; ``due_ns`` offsets from the window's
    start; ``update_id`` numbers the updates in issue order (-1 for
    reads), which indexes ``rows``."""
    kind: np.ndarray
    key: np.ndarray
    due_ns: np.ndarray
    update_id: np.ndarray
    seed: int
    width: int
    _rows: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)

    def __len__(self) -> int:
        return int(self.kind.shape[0])

    def row(self, update_id: int) -> np.ndarray:
        """The record that update ``update_id`` writes (float32, width)."""
        chunk, i = divmod(int(update_id), ROWS_PER_CHUNK)
        rows = self._rows.get(chunk)
        if rows is None:
            rng = np.random.default_rng([self.seed, 7, chunk])
            rows = rng.standard_normal((ROWS_PER_CHUNK, self.width),
                                       dtype=np.float32)
            self._rows[chunk] = rows
        return rows[i]


def make_schedule(traffic: dict, keys: int, width: int, seed: int,
                  seconds: float, rate_per_s: float,
                  root=spec.ROOT) -> Schedule:
    """All ``rate_per_s x seconds`` requests of a run, with their due
    instants (for a loop that sends on its own clock, the offsets its
    module gives)."""
    rng = np.random.default_rng([seed, 1])
    dist = distribution(traffic, root)
    loop = spec.load_part("loops", traffic["loop"], root)
    n = int(round(float(rate_per_s) * seconds))
    blocks, size = [], 0
    while size < n:
        base = dist.block(traffic, keys, len(blocks))
        blocks.append(base[rng.permutation(len(base))])
        size += len(base)
    ops = np.concatenate(blocks)[:n]
    kind, key = ops[:, 0].astype(np.int8), ops[:, 1].astype(np.int32)
    upd = kind == UPDATE
    update_id = np.full(n, -1, np.int64)
    update_id[upd] = np.arange(int(upd.sum()))
    due = loop.due_ns(traffic, rng, n, seconds)
    return Schedule(kind=kind, key=key, due_ns=due, update_id=update_id,
                    seed=int(seed), width=int(width))
