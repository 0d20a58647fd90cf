"""Function kind ``keyed_read``: one function that reads the record whose
key hash the request carries (``KV.get`` by a traced int32 hash) and
returns all of its floats.  It serves every read; its input is the hash
(int32), request key i being record i under the fill's own hash."""
import numpy as np

from bench.traffic import READ
from repro.core import enoki_function


def deploy(dep, entry) -> None:
    def handler(kv, h):
        record, _ = kv.get(h)
        return record
    enoki_function(name=entry["name"], keygroups=[dep.kg],
                   codec_width=dep.width)(handler)
    dep.place(entry["name"], entry["nodes"], np.int32(0))


def serves(dep, entry, kind, key):
    return kind == READ


def request(dep, entry, sched, i):
    return entry["name"], np.int32(dep.key_hashes[int(sched.key[i])])


def buckets(dep, entry, sched, mine, engine_buckets):
    """Every bucket, where the schedule reads (as ``scan_read``)."""
    return {entry["name"]: list(engine_buckets)} if mine.any() else {}
