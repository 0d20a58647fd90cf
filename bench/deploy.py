"""Build a configuration's deployment on the system under test: the
cluster, its keygroup, its functions, the records filled from the seed,
and the warm-up of exactly the shapes a cell's traffic reaches.

A configuration file (``bench/configs/<name>.json``) names its nodes, the
keygroup's replicas and its functions, each by kind: the kind
``bench/kinds/<kind>.py`` deploys them, says which requests of a schedule
they serve, builds each such request's input and lists the engine buckets
they reach (module docstring of ``bench/kinds/scan_read.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, spec
from repro.core import Cluster, get_function
from repro.core.engine import _valid_mask
from repro.core.keygroup import KeygroupSpec
from repro.core.network import paper_topology
from repro.core.store import Store, arena_clone, merge_snapshots_fused

TOPOLOGIES = {"paper_topology": paper_topology}
_PRIME = 2**31 - 1             # fill keys: a bijection mod this prime


def key_names(n: int) -> List[str]:
    return [f"user{i}" for i in range(n)]


def keys_are_records(config: dict) -> bool:
    """Whether the configuration's request keys are all of its records
    (else they are ``requestkeys`` named keys among them)."""
    return int(config["requestkeys"]) == int(config["recordcount"])


@dataclasses.dataclass
class Deployment:
    cluster: Cluster
    kg: str
    replicas: List[str]
    client: str
    keys: int                   # request keys
    named_keys: bool            # False: request key i is record i
    width: int
    dtype: object
    # the configuration's function entries, each with its kind's module
    functions: List[Tuple[object, dict]] = dataclasses.field(
        default_factory=list)
    fn_nodes: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    examples: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    # request key i's hash in the arena, set by ``fill``
    key_hashes: Optional[np.ndarray] = None

    def place(self, name: str, nodes: List[str], example) -> None:
        """Deploy the registered function ``name`` on ``nodes``."""
        self.cluster.deploy(get_function(name), list(nodes),
                            example_input=jax.tree.map(jnp.asarray, example))
        self.fn_nodes[name] = list(nodes)
        self.examples[name] = example


def deploy(config: dict, slots: int, dtype=None,
           root: pathlib.Path = spec.ROOT) -> Deployment:
    """The configuration's cluster with its functions deployed.  ``slots``
    is the record count held per replica; ``dtype`` overrides the record
    type (the control runs the keygroup one precision lower)."""
    kgc = config["keygroup"]
    dep = Deployment(
        cluster=Cluster(dict(config["nodes"]),
                        net=TOPOLOGIES[config["topology"]](),
                        measure_compute=False),
        kg=kgc["name"], replicas=list(kgc["replicas"]),
        client=config["client"],
        keys=slots if keys_are_records(config) else int(
            config["requestkeys"]),
        named_keys=not keys_are_records(config),
        width=int(config["record_floats"]),
        dtype=jnp.dtype(dtype or config["dtype"]))
    dep.cluster.create_keygroup(
        KeygroupSpec(name=dep.kg, slots=slots, value_width=dep.width,
                     dtype=dep.dtype), dep.replicas)
    for entry in config["functions"]:
        kind = spec.load_part("kinds", entry["kind"], root)
        kind.deploy(dep, entry)
        dep.functions.append((kind, entry))
    return dep


@functools.partial(jax.jit, static_argnames=("slots", "width", "dtype"))
def fill_values(key, slots: int, width: int, dtype):
    """The records, drawn on the device in the type they are served in."""
    return jax.random.normal(key, (slots, width), jnp.float32).astype(dtype)


def fill_layout(seed: int, slots: int, hot_keys: int,
                named: bool = True):
    """(keys, hot_slots): distinct non-zero key hashes for every slot, the
    request keys' at ``hot_slots``.  Named request keys (``user<i>``) take
    their own hashes at seeded slots; where the request keys are all the
    records (``named`` false), request key i is record i with the fill's
    own hash, and no name is hashed."""
    if not named:
        return _fresh_hashes(seed, slots).astype(np.int32), np.arange(slots)
    rng = np.random.default_rng([seed, 2])
    hot = np.array([reference.fnv1a(k) for k in key_names(hot_keys)],
                   np.int64)
    fresh = _fresh_hashes(seed, slots + hot.size)
    fresh = fresh[~np.isin(fresh, hot)][:slots]
    hot_slots = rng.choice(slots, hot.size, replace=False)
    fresh[hot_slots] = hot
    return fresh.astype(np.int32), hot_slots


def _fresh_hashes(seed: int, n: int) -> np.ndarray:
    """``n`` distinct hashes in [1, 2**31 - 1), from the seed."""
    return (np.arange(n, dtype=np.int64) * 2654435761
            + seed % _PRIME) % _PRIME + 1


def jax_seed(seed: int) -> int:
    return int(np.random.default_rng([seed, 3]).integers(2**31 - 1))


def fill(dep: Deployment, seed: int, writer: str):
    """Load every replica with the same seeded records, stamped as written
    by ``writer`` at Lamport clock 1, and note each request key's hash in
    ``dep.key_hashes``.  Returns (keys, hot_slots)."""
    nodes = dep.cluster.nodes
    with nodes[dep.replicas[0]].lock:
        slots = int(nodes[dep.replicas[0]].stores[dep.kg].keys.shape[0])
    keys, hot_slots = fill_layout(seed, slots, dep.keys,
                                  named=dep.named_keys)
    dep.key_hashes = keys[hot_slots]
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
