"""Build a configuration's deployment on the system under test: the
cluster, its keygroup, its functions, the records filled from the seed,
and the warm-up of exactly the shapes a cell's traffic reaches.

A configuration file (``bench/configs/<name>.json``) names its nodes, the
keygroup's replicas and the functions by kind; the kinds are below.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from repro.core import Cluster, enoki_function, get_function
from repro.core.engine import _valid_mask
from repro.core.keygroup import KeygroupSpec
from repro.core.network import paper_topology
from repro.core.store import Store, arena_clone, merge_snapshots_fused

TOPOLOGIES = {"paper_topology": paper_topology}
_PRIME = 2**31 - 1             # fill keys: a bijection mod this prime


def key_names(n: int) -> List[str]:
    return [f"user{i}" for i in range(n)]


def scan_read(name: str, kg: str, keys: List[str], width: int):
    """Read one of the request keys: the handler scans all of them and
    returns the row that the request's index names."""
    def handler(kv, x):
        vals, _ = kv.scan(keys)
        return vals[jnp.clip(x[0].astype(jnp.int32), 0, len(keys) - 1)]
    return enoki_function(name=name, keygroups=[kg],
                          codec_width=width)(handler)


def blind_write(name: str, kg: str, key: str, width: int):
    """Write the whole record of one literal key; returns the Lamport
    clock the write took."""
    def handler(kv, x):
        kv.set(key, x)
        return kv.state[1]
    return enoki_function(name=name, keygroups=[kg],
                          codec_width=width)(handler)


@dataclasses.dataclass
class Deployment:
    cluster: Cluster
    kg: str
    replicas: List[str]
    client: str
    read_fn: str
    update_fns: List[str]       # one per request key
    fn_nodes: Dict[str, List[str]]
    width: int
    dtype: object
    examples: Dict[str, np.ndarray]


def deploy(config: dict, slots: int, dtype=None) -> Deployment:
    """The configuration's cluster with its functions deployed.  ``slots``
    is the record count held per replica; ``dtype`` overrides the record
    type (the control runs the keygroup one precision lower)."""
    width = int(config["record_floats"])
    dtype = jnp.dtype(dtype or config["dtype"])
    kgc = config["keygroup"]
    kg = kgc["name"]
    keys = key_names(int(config["requestkeys"]))
    cluster = Cluster(dict(config["nodes"]),
                      net=TOPOLOGIES[config["topology"]](),
                      measure_compute=False)
    cluster.create_keygroup(KeygroupSpec(name=kg, slots=slots,
                                         value_width=width, dtype=dtype),
                            list(kgc["replicas"]))
    read_fn, update_fns, fn_nodes, examples = None, [], {}, {}
    for f in config["functions"]:
        if f["kind"] == "scan_read":
            scan_read(f["name"], kg, keys, width)
            names, ex = [f["name"]], np.zeros((1,), np.float32)
            read_fn = f["name"]
        elif f["kind"] == "blind_write":
            names = [f"{f['name']}_{i}" for i in range(len(keys))]
            for name, key in zip(names, keys):
                blind_write(name, kg, key, width)
            ex = np.zeros((width,), np.float32)
            update_fns = names
        else:
            raise ValueError(f"unknown function kind {f['kind']!r}")
        for name in names:
            cluster.deploy(get_function(name), list(f["nodes"]),
                           example_input=jnp.asarray(ex))
            fn_nodes[name] = list(f["nodes"])
            examples[name] = ex
    return Deployment(cluster=cluster, kg=kg, replicas=list(kgc["replicas"]),
                      client=config["client"], read_fn=read_fn,
                      update_fns=update_fns, fn_nodes=fn_nodes, width=width,
                      dtype=dtype, examples=examples)


@functools.partial(jax.jit, static_argnames=("slots", "width", "dtype"))
def fill_values(key, slots: int, width: int, dtype):
    """The records, drawn on the device in the type they are served in."""
    return jax.random.normal(key, (slots, width), jnp.float32).astype(dtype)


def fill_layout(seed: int, slots: int, hot_keys: int):
    """(keys, hot_slots): distinct non-zero key hashes for every slot, the
    request keys' own hashes at seeded slots."""
    rng = np.random.default_rng([seed, 2])
    hot = np.array([reference.fnv1a(k) for k in key_names(hot_keys)],
                   np.int64)
    fresh = (np.arange(slots + hot.size, dtype=np.int64) * 2654435761
             + seed % _PRIME) % _PRIME + 1
    fresh = fresh[~np.isin(fresh, hot)][:slots]
    hot_slots = rng.choice(slots, hot.size, replace=False)
    fresh[hot_slots] = hot
    return fresh.astype(np.int32), hot_slots


def jax_seed(seed: int) -> int:
    return int(np.random.default_rng([seed, 3]).integers(2**31 - 1))


def fill(dep: Deployment, seed: int, writer: str, hot_keys: int):
    """Load every replica with the same seeded records, stamped as written
    by ``writer`` at Lamport clock 1.  Returns (keys, hot_slots)."""
    nodes = dep.cluster.nodes
    with nodes[dep.replicas[0]].lock:
        slots = int(nodes[dep.replicas[0]].stores[dep.kg].keys.shape[0])
    keys, hot_slots = fill_layout(seed, slots, hot_keys)
    writer_id = nodes[writer].node_id
    vv = np.zeros(reference.NODES_PACKED, np.int32)
    vv[writer_id] = 1
    arena = Store(
        keys=jnp.asarray(keys),
        values=fill_values(jax.random.key(jax_seed(seed)), slots, dep.width,
                           dep.dtype),
        lengths=jnp.full((slots,), dep.width, jnp.int32),
        versions=jnp.full((slots,), int(reference.pack(1, writer_id)),
                          jnp.int32),
        vv=jnp.asarray(vv))
    for i, node in enumerate(dep.replicas):
        nd = nodes[node]
        with nd.lock:
            nd.stores[dep.kg] = arena if i == 0 else arena_clone(arena)
    return keys, hot_slots


def warm(dep: Deployment, buckets: Dict[str, List[int]],
         merge_ks=(1, 2, 4, 8, 16)) -> int:
    """Run every shape the window will reach once, on scratch copies of the
    arena: each function at its buckets, the replication clone, and, on a
    keygroup with more than one replica, the fused merge at each K the
    program pads to, up to 16 (32 stacked deployment-size arenas would not
    fit the chip).
    Returns the number of executions."""
    c = dep.cluster
    eng = c.engine
    count = 0
    store_node = dep.replicas[0]
    with c.nodes[store_node].lock:
        proto = c.nodes[store_node].stores[dep.kg]
    for fn, bs in buckets.items():
        for node in dep.fn_nodes[fn]:
            nd = c.nodes[node]
            bh = nd.batched_handlers[fn]
            for b in bs:
                xs = jax.tree.map(jnp.asarray, eng._stage_chunk(
                    [dep.examples[fn]] * b, b))
                scratch = jax.tree.map(jnp.zeros_like, proto)
                out = bh(scratch, nd.clock, xs, _valid_mask(b, b),
                         independent=False)
                jax.block_until_ready(out[:3])
                count += 1
    jax.block_until_ready(arena_clone(proto))
    count += 1
    if len(dep.replicas) > 1:
        aligned = c._aligned.get(dep.kg, False)
        for k in merge_ks:
            acc = jax.tree.map(jnp.zeros_like, proto)
            jax.block_until_ready(merge_snapshots_fused(
                acc, (proto,) * k, aligned=aligned))
            count += 1
    return count
