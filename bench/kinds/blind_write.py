"""Function kind ``blind_write``: one function per request key, each
writing the whole record of its literal key and returning the Lamport
clock the write took.  They serve every update."""
import numpy as np

from bench.deploy import key_names
from bench.traffic import UPDATE
from repro.core import enoki_function


def _name(entry, k: int) -> str:
    return f"{entry['name']}_{k}"


def deploy(dep, entry) -> None:
    for k, key in enumerate(key_names(dep.keys)):
        def handler(kv, x, key=key):
            kv.set(key, x)
            return kv.state[1]
        enoki_function(name=_name(entry, k), keygroups=[dep.kg],
                       codec_width=dep.width)(handler)
        dep.place(_name(entry, k), entry["nodes"],
                  np.zeros((dep.width,), np.float32))


def serves(dep, entry, kind, key):
    return kind == UPDATE


def request(dep, entry, sched, i):
    return (_name(entry, int(sched.key[i])),
            sched.row(int(sched.update_id[i])))


def buckets(dep, entry, sched, mine, engine_buckets):
    """Every bucket, for each key the schedule updates."""
    return {_name(entry, int(k)): list(engine_buckets)
            for k in np.unique(sched.key[mine])}
