"""Function kind ``field_update``: one function that updates one field of
the record whose key hash the request carries.  It reads the record,
replaces field ``f`` (``FIELDCOUNT`` equal fields of ``width //
FIELDCOUNT`` floats) with the payload, writes the record back by the same
key and returns the Lamport clock the write took, or -1 where the arena
holds no such key (the write then changed nothing).  It serves every
update.

Its input is ``(hash, f, payload)``: the key hash (int32), the field and
the update's payload, ``sched.row(u)[:width // FIELDCOUNT]``, unique per
update.  The field comes from the update id ``u`` by a fixed rule:
``f = u % FIELDCOUNT``, so every field takes a tenth of the updates.
"""
import jax
import numpy as np

from bench.traffic import UPDATE
from repro.core import enoki_function

FIELDCOUNT = 10


def field_floats(dep) -> int:
    if dep.width % FIELDCOUNT:
        raise ValueError(f"a record of {dep.width} floats does not split "
                         f"into {FIELDCOUNT} fields")
    return dep.width // FIELDCOUNT


def field_of(update_id: int) -> int:
    return int(update_id) % FIELDCOUNT


def deploy(dep, entry) -> None:
    ff = field_floats(dep)

    def handler(kv, x):
        h, f, payload = x
        record, _ = kv.get(h)
        record = jax.lax.dynamic_update_slice(
            record, payload.astype(record.dtype), (f * ff,))
        ok = kv.set(h, record)
        return jax.numpy.where(ok, kv.state[1], -1)
    enoki_function(name=entry["name"], keygroups=[dep.kg],
                   codec_width=dep.width)(handler)
    dep.place(entry["name"], entry["nodes"],
              (np.int32(0), np.int32(0), np.zeros((ff,), np.float32)))


def serves(dep, entry, kind, key):
    return kind == UPDATE


def request(dep, entry, sched, i):
    u = int(sched.update_id[i])
    return entry["name"], (np.int32(dep.key_hashes[int(sched.key[i])]),
                           np.int32(field_of(u)),
                           sched.row(u)[:field_floats(dep)])


def buckets(dep, entry, sched, mine, engine_buckets):
    """Every bucket, where the schedule updates (as ``scan_read``)."""
    return {entry["name"]: list(engine_buckets)} if mine.any() else {}
