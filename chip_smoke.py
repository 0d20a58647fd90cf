"""Chip smoke: serve one replicated keygroup of deployment size on one TPU.

    python chip_smoke.py [--seed N]

Drives the served path once — ``Cluster`` -> ``engine`` -> ``FaasServer``
— on the paper's topology (edge, edge2, cloud).  One REPLICATED keygroup
on edge and edge2 holds 262,144 slots of 1 KiB float32 records (YCSB's
record size, 256 MiB per replica), filled from ``--seed`` on the device.
A read-only ``get`` is deployed on both edges and a read-modify-write
accumulator on edge; a client at edge2 sends a few hundred requests
open-loop, so reads fold edge's replicated snapshots in at edge2 through
the ``enoki_merge_rows`` kernel.  Every output and both replicas' final
contents are checked against a plain sequential reference.  Before that,
the kernel alone is held to its plain reference on random versions, at
the smoke's arena and at one padded across several row tiles.

Exits non-zero, printing no result line, when the default device is not a
TPU or when any phase fails.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
One process; nothing is started that touches the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
from typing import Dict, List, Tuple

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from repro.core import Cluster, enoki_function, get_function  # noqa: E402
from repro.core.keygroup import KeygroupSpec            # noqa: E402
from repro.core.network import paper_topology           # noqa: E402
from repro.core.store import (Store, arena_clone, merge_many_fn,  # noqa: E402
                              stores_equal)
from repro.core.versioning import MAX_NODES, fnv1a, pack_version  # noqa: E402
from repro.kernels.enoki_merge.kernel import enoki_merge_rows  # noqa: E402
from repro.kernels.enoki_merge.ref import enoki_merge_ref  # noqa: E402
from repro.launch.faas_server import FaasServer         # noqa: E402

KG = "smokekg"
SLOTS = 262_144                 # 256 MiB of 1 KiB records per replica
WIDTH = 256                     # float32 lanes per record
HOT = [f"user{i}" for i in range(8)]    # the keys `get` reads
ACC = "acc"                     # the key the accumulator updates
N_REQUESTS = 384
RMW_EVERY = 4                   # one request in four is a read-modify-write
_PRIME = 2**31 - 1              # fill keys: a bijection mod this prime
# the kernel alone against its plain reference: the smoke's arena, and
# one padded to whole row tiles across several of them
KERNEL_SHAPES = [(SLOTS, WIDTH), (3000, WIDTH)]


@enoki_function(name="smoke_get", keygroups=[KG], codec_width=WIDTH)
def smoke_get(kv, x):
    vals, _ = kv.scan(HOT)
    return vals[jnp.clip(x[0].astype(jnp.int32), 0, len(HOT) - 1)]


@enoki_function(name="smoke_rmw", keygroups=[KG], codec_width=WIDTH)
def smoke_rmw(kv, x):
    cur, _ = kv.get(ACC)
    kv.set(ACC, cur + x)
    return cur + x


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — the default device is "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


def deploy(slots: int = SLOTS) -> Cluster:
    cluster = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                      net=paper_topology(), measure_compute=False)
    cluster.create_keygroup(KeygroupSpec(name=KG, slots=slots,
                                         value_width=WIDTH),
                            ["edge", "edge2"])
    cluster.deploy(get_function("smoke_get"), ["edge", "edge2"],
                   example_input=jnp.zeros((1,), jnp.float32))
    cluster.deploy(get_function("smoke_rmw"), ["edge"],
                   example_input=jnp.zeros((WIDTH,), jnp.float32))
    return cluster


@dataclasses.dataclass
class Reference:
    """Sequential model of one replica: the seeded fill plus a plain dict
    of the writes since, ``{key_hash: (version, length, row)}``, applied
    in order with the store's LWW and Lamport rules."""
    keys: np.ndarray
    values: np.ndarray
    version: int                # every filled slot's packed version
    vv: np.ndarray
    slot: Dict[int, int]        # key hash -> slot
    writes: Dict[int, Tuple[int, int, np.ndarray]] = dataclasses.field(
        default_factory=dict)
    clock: int = 0

    def read(self, key: str) -> np.ndarray:
        h = fnv1a(key)
        if h in self.writes:
            return self.writes[h][2]
        return self.values[self.slot[h]]

    def rmw(self, key: str, x: np.ndarray, node_id: int) -> np.ndarray:
        row = self.read(key) + x
        self.clock = max(self.clock, int(self.vv.max())) + 1
        self.vv[node_id] = max(self.vv[node_id], self.clock)
        self.writes[fnv1a(key)] = (int(pack_version(self.clock, node_id)),
                                   row.shape[0], row)
        return row

    def expected(self) -> Store:
        values = self.values.copy()
        lengths = np.full(self.keys.shape, values.shape[1], np.int32)
        versions = np.full(self.keys.shape, self.version, np.int32)
        for h, (ver, length, row) in self.writes.items():
            i = self.slot[h]
            values[i], lengths[i], versions[i] = row, length, ver
        return Store(keys=self.keys, values=values, lengths=lengths,
                     versions=versions, vv=self.vv)


def fill(cluster: Cluster, seed: int) -> Reference:
    """Load every replica with the same seeded records in one slot layout:
    the keys the handlers registered at deploy keep their slots, every
    other slot gets a distinct key, and all records are written as if by
    edge at Lamport clock 1.  Values are drawn on the device."""
    edge = cluster.nodes["edge"]
    with edge.lock:
        keys = np.asarray(edge.stores[KG].keys).copy()
    slots = keys.shape[0]
    taken = keys[keys != 0]
    fresh = (np.arange(slots + taken.size, dtype=np.int64) * 2654435761
             + seed) % _PRIME + 1
    fresh = fresh[~np.isin(fresh, taken)][:slots - taken.size]
    keys[keys == 0] = fresh
    values = jax.random.normal(jax.random.key(seed), (slots, WIDTH),
                               jnp.float32)
    version = int(pack_version(1, edge.node_id))
    vv = np.zeros(MAX_NODES, np.int32)
    vv[edge.node_id] = 1
    arena = Store(keys=jnp.asarray(keys, jnp.int32), values=values,
                  lengths=jnp.full((slots,), WIDTH, jnp.int32),
                  versions=jnp.full((slots,), version, jnp.int32),
                  vv=jnp.asarray(vv))
    for node in sorted(cluster.naming.replicas_of(KG)):
        nd = cluster.nodes[node]
        with nd.lock:
            nd.stores[KG] = arena_clone(arena)
    return Reference(keys=keys.astype(np.int32), values=np.asarray(values),
                     version=version, vv=vv,
                     slot={int(keys[i]): int(i)
                           for i in np.flatnonzero(np.isin(keys, taken))})


def check_kernel(shapes, seed: int) -> List[str]:
    """``enoki_merge_rows`` against ``enoki_merge_ref`` on random rows
    and versions with many ties, exact, at each (rows, width)."""
    merge = jax.jit(enoki_merge_rows)
    problems = []
    for rows, width in shapes:
        ks = jax.random.split(jax.random.key(seed), 4)
        a = jax.random.normal(ks[0], (rows, width), jnp.float32)
        b = jax.random.normal(ks[1], (rows, width), jnp.float32)
        a_ver = jax.random.randint(ks[2], (rows,), 0, 50, jnp.int32)
        b_ver = jax.random.randint(ks[3], (rows,), 0, 50, jnp.int32)
        got = merge(a, a_ver, b, b_ver)
        want = enoki_merge_ref(a, a_ver, b, b_ver)
        if not all(bool(jnp.array_equal(g, w)) for g, w in zip(got, want)):
            problems.append(f"merge kernel differs from its reference at "
                            f"{rows} x {width}")
    return problems


def merge_has_kernel(cluster: Cluster) -> bool:
    """Whether the fused aligned delivery merge lowers to the Pallas
    kernel (``tpu_custom_call``) rather than an interpreted loop."""
    edge = cluster.nodes["edge"]
    with edge.lock:
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape,
                                                             a.dtype),
                              edge.stores[KG])
    return "tpu_custom_call" in merge_many_fn(True).lower(
        shapes, (shapes,)).as_text()


def serve(cluster: Cluster, ref: Reference, seed: int,
          n_requests: int = N_REQUESTS, spacing_s: float = 1e-3,
          timeout_s: float = 600.0) -> Tuple[FaasServer, List[str]]:
    """Open loop from a client at edge2: reads go to edge2 (its nearest
    deployment), read-modify-writes to edge (the only one).  Returns the
    stopped server and every output that disagreed with the reference."""
    rng = np.random.default_rng(seed)
    edge_id = cluster.nodes["edge"].node_id
    sent = []
    with FaasServer(cluster, window_ms=8.0, client="edge2") as srv:
        for i in range(n_requests):
            if i % RMW_EVERY == RMW_EVERY - 1:
                x = rng.integers(-8, 8, WIDTH).astype(np.float32)
                want = ref.rmw(ACC, x, edge_id)
                fut = srv.submit("smoke_rmw", x)
            else:
                k = int(rng.integers(len(HOT)))
                want = ref.read(HOT[k])
                fut = srv.submit("smoke_get", np.float32([k]))
            sent.append((i, fut, want))
            time.sleep(spacing_s)
        bad = []
        for i, fut, want in sent:
            try:
                got = np.asarray(fut.result(timeout=timeout_s).output)
            except Exception as e:      # lost: counted by the server too
                bad.append(f"request {i}: {e!r}")
                continue
            if not np.array_equal(got, want):
                bad.append(f"request {i}: output differs from the reference")
    return srv, bad


def compare(cluster: Cluster, ref: Reference) -> List[str]:
    """Both replicas against the reference, leaf by leaf, and each other."""
    want = ref.expected()
    problems = []
    stores = {}
    for node in sorted(cluster.naming.replicas_of(KG)):
        stores[node] = cluster.store_of(KG, node)
        got = jax.device_get(stores[node])
        for name, a, b in zip(Store._fields, got, want):
            if not np.array_equal(a, b):
                problems.append(f"{node}: {name} differs from the reference")
    a, b = stores.values()
    if not stores_equal(a, b):
        problems.append("edge and edge2 are not stores_equal")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    devices = jax.devices()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    problems = check_kernel(KERNEL_SHAPES, args.seed)
    print(f"merge kernel vs reference at {KERNEL_SHAPES}: "
          f"{'agrees' if not problems else 'DISAGREES'} in "
          f"{time.perf_counter() - t0:.3f} s")

    cluster = deploy()
    ref = fill(cluster, args.seed)
    arena_bytes = sum(int(x.nbytes)
                      for x in cluster.nodes["edge"].stores[KG])
    print(f"arena: {SLOTS} slots x {WIDTH} float32 = {arena_bytes} bytes "
          f"per replica, replicas on edge and edge2")
    kernel = merge_has_kernel(cluster)
    print(f"fused merge lowers to tpu_custom_call: {kernel}")

    t0 = time.perf_counter()
    runs = cluster.engine.prewarm()
    print(f"prewarm: {runs} executions in "
          f"{time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    srv, bad = serve(cluster, ref, args.seed)
    problems += bad
    cluster.flush_replication()
    st, cs = srv.stats, cluster.stats
    print(f"serve: {N_REQUESTS} requests, served={st.served} lost={st.lost} "
          f"cycle_errors={st.cycle_errors} in "
          f"{time.perf_counter() - t0:.3f} s (host clock)")
    print(f"merges: dispatches={cs.merge_dispatches} "
          f"snapshots={cs.merge_snapshots} aligned={cs.merge_aligned} "
          f"fallback={cs.merge_fallback}")
    errors = list(cluster.engine.errors)
    print(f"engine errors: {len(errors)}"
          + "".join(f"\n  {e!r}" for e in errors))

    problems += compare(cluster, ref)
    if not kernel:
        problems.append("the fused merge does not run the Pallas kernel")
    if st.served != N_REQUESTS or st.lost or st.cycle_errors:
        problems.append("requests were lost or cycles failed")
    if cs.merge_aligned == 0 or cs.merge_fallback:
        problems.append("replication did not merge on the aligned kernel")
    if errors:
        problems.append("the engine recorded flush-cycle errors")
    print(f"reference: {'agrees' if not problems else 'DISAGREES'} "
          f"({len(ref.writes)} keys written, both replicas compared)")
    for p in problems:
        print(f"FAIL: {p}")
    if problems:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
