"""Function kind ``scan_read``: one function that reads a request key by
scanning all of them and returning the row the request's index names.  It
serves every read.

A kind module (``bench/kinds/<kind>.py``, named by a configuration's
``functions[].kind``) provides four functions, each given the deployment
and the configuration's entry for it:

* ``deploy(dep, entry)``: register its functions and deploy them
  (``dep.place``) on ``entry["nodes"]``;
* ``serves(dep, entry, kind, key)``: which schedule entries, by request
  kind and key (arrays), it serves; each entry is served by one kind;
* ``request(dep, entry, sched, i)``: ``(function, input)`` of served
  entry ``i``; the input may be a pytree;
* ``buckets(dep, entry, sched, mine, engine_buckets)``: the engine
  buckets each of its functions can reach, given the mask of the entries
  it serves.
"""
import jax.numpy as jnp
import numpy as np

from bench.deploy import key_names
from bench.traffic import READ
from repro.core import enoki_function


def deploy(dep, entry) -> None:
    keys = key_names(dep.keys)

    def handler(kv, x):
        vals, _ = kv.scan(keys)
        return vals[jnp.clip(x[0].astype(jnp.int32), 0, len(keys) - 1)]
    enoki_function(name=entry["name"], keygroups=[dep.kg],
                   codec_width=dep.width)(handler)
    dep.place(entry["name"], entry["nodes"], np.zeros((1,), np.float32))


def serves(dep, entry, kind, key):
    return kind == READ


def request(dep, entry, sched, i):
    return entry["name"], np.float32([int(sched.key[i])])


def buckets(dep, entry, sched, mine, engine_buckets):
    """Every bucket, where the schedule reads: a host that stalls for a
    moment lets any number of requests gather, and the engine cuts a
    batch at its largest bucket."""
    return {entry["name"]: list(engine_buckets)} if mine.any() else {}
