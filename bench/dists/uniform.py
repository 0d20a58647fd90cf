"""Uniform keys: every block holds exactly ``readproportion`` of its
``block`` operations as reads and the rest as updates, each kind spread
evenly over the request keys by largest remainder.  Every block is the
same multiset."""
import numpy as np

from bench.traffic import READ, UPDATE, apportion


def block(traffic: dict, keys: int, index: int) -> np.ndarray:
    """The (kind, key) multiset of block ``index``, unshuffled:
    (block, 2)."""
    n = int(traffic["block"])
    n_read = int(round(n * float(traffic["readproportion"])))
    shares = np.full(keys, 1.0 / keys)
    out = []
    for kind, count in ((READ, n_read), (UPDATE, n - n_read)):
        for key, c in enumerate(apportion(count, shares)):
            out.extend([(kind, key)] * int(c))
    return np.asarray(out, np.int64).reshape(-1, 2)
