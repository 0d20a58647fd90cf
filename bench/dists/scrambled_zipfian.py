"""YCSB's scrambled Zipfian keys (``ScrambledZipfianGenerator``): a rank
drawn by YCSB's ``ZipfianGenerator`` (Gray et al., "Quickly generating
billion-record synthetic databases", with constant ``zipfianconstant``),
then key = FNV-1a-64(rank) mod ``keys``, so the popular keys are spread
over the keyspace instead of packed at its start.

As in YCSB, the rank is drawn over ``ITEM_COUNT`` = 10**10 items (the
generator's ``items`` is max - min + 1 for min 0, max ``ITEM_COUNT``)
with the precomputed ``ZETAN`` = zeta(10**10, 0.99), whatever the record
count: the hottest key takes about 1/ZETAN, 3.8% of requests.  YCSB uses
that constant only for ``zipfianconstant`` 0.99; this module serves no
other constant.

Every block holds exactly ``readproportion`` of its ``block`` operations
as reads and the rest as updates (as ``uniform``); its keys are drawn
from a stream of the block index alone, which ``--seed`` does not touch.
"""
import numpy as np

from bench.traffic import READ, UPDATE

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
_STREAM = 0x59435342                # the key streams' own tag
ITEM_COUNT = 10**10                 # ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302           # ScrambledZipfianGenerator.ZETAN
THETA = 0.99                        # the constant ZETAN was computed for


def ranks(u: np.ndarray, n: int, theta: float, zetan: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextValue`` over ``n`` items whose
    zeta(n, theta) is ``zetan``, for uniform draws ``u`` in [0, 1): rank
    0 is the most popular."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    body = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    out = np.where(uz < zeta2, 1, np.minimum(body, n - 1))
    return np.where(uz < 1.0, 0, out)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 low-first octets of
    each value, as a non-negative 64-bit number (Java's ``Math.abs``)."""
    v = np.asarray(v, np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for i in range(8):
        h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
    neg = h >= np.uint64(1 << 63)
    return np.where(neg, ~h + np.uint64(1), h)


def keys_of(u: np.ndarray, keys: int, theta: float) -> np.ndarray:
    """``ScrambledZipfianGenerator.nextValue`` over ``keys`` records."""
    if theta != THETA:
        raise ValueError(f"scrambled_zipfian serves zipfianconstant "
                         f"{THETA} only (YCSB's precomputed ZETAN), "
                         f"not {theta}")
    rank = ranks(u, ITEM_COUNT + 1, theta, ZETAN)
    return (fnvhash64(rank) % np.uint64(keys)).astype(np.int64)


def block(traffic: dict, keys: int, index: int) -> np.ndarray:
    """The (kind, key) multiset of block ``index``, unshuffled:
    (block, 2)."""
    n = int(traffic["block"])
    n_read = int(round(n * float(traffic["readproportion"])))
    u = np.random.default_rng([_STREAM, index]).random(n)
    key = keys_of(u, keys, float(traffic["zipfianconstant"]))
    kind = np.where(np.arange(n) < n_read, READ, UPDATE)
    return np.stack([kind, key], axis=1).astype(np.int64)
