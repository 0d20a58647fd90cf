"""Fig 4 reproduction: read/write throughput — item-size sweep AND the
batched-invocation sweep.

The paper drives a closed workload (100 client threads, 2 min) against a
read function and a write function with item sizes 1 B … 1 MB.  Two views:

1. **Size sweep** (the paper's figure): per-op local store cost is MEASURED
   (real jitted arena ops on this host); closed-loop throughput then follows
   Little's law with the network model:

       latency(size)   = client_rtt + per-op network (placement) + compute
       tasks/s         = threads / latency,     capped by link bandwidth
       MB/s            = tasks/s × size

   Expected shapes (paper §4.2): cloud reads saturate the 12.5 MB/s
   (100 Mb/s) edge-cloud link for items ≳100 kB; edge reads keep scaling.

2. **Batch sweep** (this repo's §4.2 hot-path work): wall-clock ops/s of a
   REAL Enoki node serving stateful get/set functions, sweeping the batched
   invocation engine over batch sizes {1, 8, 64, 256}.  batch=1 is the
   sequential ``Cluster.invoke`` baseline (one Python round-trip + one
   device dispatch per request); larger batches go through
   ``Cluster.invoke_batch`` (one dispatch per batch).  The speedup is pure
   per-invocation overhead removed — exactly the bottleneck the batching
   engine targets.

3. **Window sweep** (the background-flusher model, §4.2 × §4.3): instead of
   handing the engine pre-formed batches, clients ``submit`` a fixed
   arrival-rate stream and the engine's arrival-time windows coalesce it —
   window_ms × node-count grid.  Batch size is EMERGENT (≈ rate ×
   window_ms per node) and a multi-node run drains all nodes' windows in
   one flush cycle (cross-node fan-out, parallel timelines).  The check the
   acceptance pins: a 2-node windowed run at a 64-deep window sustains at
   least the single-node batch-64 ops/s of the explicit batch sweep.

4. **Hedge sweep** (PR 3): open-loop read arrivals against a STRAGGLER
   topology (the nearest replica serves slowly), windowed hedging off vs
   on, driven pump-by-deadline through the router's batched path.  The
   acceptance check: hedged p99 <= unhedged p99, plus hedge counters.

5. **Serving sweep** (PR 3): the REAL wall-clock serving loop
   (``launch/faas_server.py``), open-loop (fixed wall arrival rate) and
   closed-loop (client threads re-submitting on completion) — virtual
   latency percentiles and wall ops/s.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import interleaved_repeats, median_ops
from repro.core import Cluster, enoki_function, get_function
from repro.core.network import paper_topology
from repro.core.store import kv_get, kv_set, store_new
from repro.core.versioning import MAX_NODES, fnv1a

SIZES = [1, 100, 1_000, 10_000, 100_000, 1_000_000]
THREADS = 100
BATCH_SIZES = [1, 8, 64, 256]
BATCH_ITEM_WIDTH = 64          # float32 payload width for the batch sweep
BATCH_REQUESTS = 512


# ---------------------------------------------------------------------------
# The batch-sweep workload functions (real stateful handlers)
# ---------------------------------------------------------------------------

@enoki_function(name="fig4_read", keygroups=["fig4kg"],
                codec_width=BATCH_ITEM_WIDTH)
def fig4_read(kv, x):
    val, found = kv.get("item")
    return val[:1]


@enoki_function(name="fig4_write", keygroups=["fig4kg"],
                codec_width=BATCH_ITEM_WIDTH)
def fig4_write(kv, x):
    cur, _ = kv.get("item")
    kv.set("item", cur + x)
    return x[:1]


def _measure_local_op_ms(size: int, op: str) -> float:
    """Median wall time of a jitted arena get/set at this payload size."""
    width = max(1, size)
    store = store_new(4, width, MAX_NODES, dtype=jnp.uint8)
    h = fnv1a("k")
    row = jnp.zeros((width,), jnp.uint8)
    clock = jnp.zeros((), jnp.int32)

    if op == "set":
        fn = jax.jit(lambda s, c: kv_set(s, h, row, width, c, 0))
        out = fn(store, clock)
        jax.block_until_ready(out[0])
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            out = fn(store, clock)
            jax.block_until_ready(out[0])
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(ts))
    store, clock, _ = kv_set(store, h, row, width, clock, 0)
    fn = jax.jit(lambda s: kv_get(s, h))
    out = fn(store)
    jax.block_until_ready(out[0])
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn(store)
        jax.block_until_ready(out[0])
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


def run_size_sweep():
    net = paper_topology()
    rows = []
    for op in ("read", "write"):
        for size in SIZES:
            local_ms = _measure_local_op_ms(size, "get" if op == "read"
                                            else "set")
            for placement in ("edge", "cloud"):
                lan = net.link("client", "edge")
                # client->edge function invocation (tiny request payload)
                lat = lan.rtt_ms + lan.transfer_ms(64)
                if placement == "cloud":
                    link = net.link("edge", "cloud")
                    lat += link.rtt_ms + link.transfer_ms(size)
                    cap_mbs = link.bandwidth_mbps / 8.0
                else:
                    cap_mbs = float("inf")
                lat += local_ms
                tps = THREADS / (lat / 1e3)
                mbs = tps * size / 1e6
                if mbs > cap_mbs:          # link saturation (fig 4a ceiling)
                    mbs = cap_mbs
                    tps = mbs * 1e6 / size
                rows.append({"op": op, "size_B": size, "store": placement,
                             "latency_ms": round(lat, 2),
                             "tasks_per_s": round(tps, 1),
                             "MB_per_s": round(mbs, 2)})
    return rows


# ---------------------------------------------------------------------------
# Batch sweep: the batched invocation engine on a real node
# ---------------------------------------------------------------------------

def _drive(cluster: Cluster, fn_name: str, batch: int,
           n_requests: int) -> float:
    """Wall-clock ops/s for ``n_requests`` invocations at ``batch`` size
    (batch 1 = the sequential invoke path), blocking until the store state
    is actually materialised."""
    x = np.ones((BATCH_ITEM_WIDTH,), np.float32)
    xs = [x] * max(batch, 1)

    def block():
        jax.block_until_ready(cluster.nodes["edge"].stores["fig4kg"])

    # warm the jit caches for every bucket the timed loop will hit
    # (including the ragged tail's smaller bucket)
    if batch == 1:
        cluster.invoke(fn_name, "edge", x)
    else:
        cluster.invoke_batch(fn_name, "edge", xs)
        tail = n_requests % batch
        if tail:
            cluster.invoke_batch(fn_name, "edge", xs[:tail])
    block()

    # every path must MATERIALISE its responses (a serving node replies with
    # bytes, not a lazy device array); invoke_batch already does internally
    t0 = time.perf_counter()
    if batch == 1:
        for i in range(n_requests):
            r = cluster.invoke(fn_name, "edge", x, t_send=float(i))
            np.asarray(r.output)
    else:
        for lo in range(0, n_requests, batch):
            bs = min(batch, n_requests - lo)   # ragged tail: no extra ops
            cluster.invoke_batch(fn_name, "edge", xs[:bs],
                                 t_sends=[float(lo + j)
                                          for j in range(bs)])
    block()
    return n_requests / (time.perf_counter() - t0)


def run_batch_sweep(batch_sizes=tuple(BATCH_SIZES),
                    n_requests: int = BATCH_REQUESTS):
    cluster = Cluster({"edge": "edge", "cloud": "cloud"},
                      net=paper_topology(), measure_compute=False)
    cluster.deploy(get_function("fig4_read"), ["edge"])
    cluster.deploy(get_function("fig4_write"), ["edge"])
    batch_sizes = sorted(set(batch_sizes))   # baseline = smallest batch
    rows = []
    for op, fn_name in (("read", "fig4_read"), ("write", "fig4_write")):
        base = None
        for b in batch_sizes:
            ops_s = _drive(cluster, fn_name, b, n_requests)
            if base is None:
                base = ops_s
            rows.append({"op": op, "batch": b,
                         "ops_per_s": round(ops_s, 1),
                         "base_batch": batch_sizes[0],
                         "speedup_vs_base": round(ops_s / base, 2)})
    return rows


# ---------------------------------------------------------------------------
# Window sweep: the async background flusher across nodes
# ---------------------------------------------------------------------------

WINDOW_SIZES_MS = [4.0, 16.0, 64.0, 256.0]   # at 1 req/ms/node:
                                             # batches of ~4/16/64/256
WINDOW_NODE_COUNTS = [1, 2]
WINDOW_RATE_PER_MS = 1.0                # arrival rate per node


def _drive_windowed(cluster: Cluster, fn_name: str, nodes, window_ms: float,
                    n_requests: int, rate_per_ms: float) -> dict:
    """Submit a fixed-rate arrival stream round-robin across ``nodes`` and
    let the engine's arrival-time windows form the batches; one pump drains
    every window (multi-node windows of a cycle fan out in parallel
    timelines).  Returns wall-clock ops/s plus the emergent batch shape."""
    from repro.core.engine import BatchedInvocationEngine
    x = np.ones((BATCH_ITEM_WIDTH,), np.float32)

    def block():
        for nd in nodes:
            jax.block_until_ready(cluster.nodes[nd].stores["fig4kg"])

    cluster.flush_replication()
    block()
    cluster.engine = BatchedInvocationEngine(cluster, window_ms=window_ms)
    eng = cluster.engine
    spacing = 1.0 / (rate_per_ms * len(nodes))   # global inter-arrival (ms)
    t0 = time.perf_counter()
    for i in range(n_requests):
        eng.submit(fn_name, nodes[i % len(nodes)], x, t_send=i * spacing)
    out = eng.pump()
    block()
    elapsed = time.perf_counter() - t0
    assert len(out) == n_requests
    st = eng.stats
    return {"ops_per_s": n_requests / elapsed,
            "windows": st.windows_flushed,
            "avg_batch": round(n_requests / max(1, st.windows_flushed), 1),
            "dispatches": st.dispatches}


def run_window_sweep(window_sizes=tuple(WINDOW_SIZES_MS),
                     node_counts=tuple(WINDOW_NODE_COUNTS),
                     n_requests: int = BATCH_REQUESTS,
                     rate_per_ms: float = WINDOW_RATE_PER_MS):
    rows = []
    for nodes_n in node_counts:
        cluster = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                          net=paper_topology(), measure_compute=False)
        nodes = ["edge", "edge2"][:nodes_n]
        cluster.deploy(get_function("fig4_read"), nodes)
        cluster.deploy(get_function("fig4_write"), nodes)
        # warm every bucket the emergent window sizes can land in, per node
        # (jit caches live on the deployed handlers, so this is once per
        # cluster, outside the timed loops)
        x = np.ones((BATCH_ITEM_WIDTH,), np.float32)
        from repro.core.engine import DEFAULT_BUCKETS
        for fn_name in ("fig4_read", "fig4_write"):
            for nd in nodes:
                for b in DEFAULT_BUCKETS:
                    cluster.invoke_batch(fn_name, nd, [x] * b)
        for op, fn_name in (("read", "fig4_read"), ("write", "fig4_write")):
            for w in window_sizes:
                m = _drive_windowed(cluster, fn_name, nodes, w, n_requests,
                                    rate_per_ms)
                rows.append({"op": op, "window_ms": w, "nodes": nodes_n,
                             "ops_per_s": round(m["ops_per_s"], 1),
                             "windows": m["windows"],
                             "avg_batch": m["avg_batch"],
                             "dispatches": m["dispatches"]})
    return rows


# ---------------------------------------------------------------------------
# Hedge sweep: windowed hedging on the straggler topology (batched path)
# ---------------------------------------------------------------------------

HEDGE_REQUESTS = 128
HEDGE_WINDOW_MS = 16.0
HEDGE_AFTER_MS = 4.0
HEDGE_STRAGGLER_MS = 60.0       # compute charge at the overloaded nearest
                                # replica (edge); edge2 stays fast
HEDGE_RATE_PER_MS = 0.25        # open-loop arrivals: one every 4 virtual ms


def _seed_and_warm(cluster: Cluster, nodes):
    """Seed the read item and warm every jit bucket outside timed regions."""
    from repro.core.engine import DEFAULT_BUCKETS
    x = np.ones((BATCH_ITEM_WIDTH,), np.float32)
    for nd in nodes:
        cluster.invoke("fig4_write", nd, x)
        for b in DEFAULT_BUCKETS:
            cluster.invoke_batch("fig4_read", nd, [x] * b)
    cluster.flush_replication()
    return x


def run_hedge_sweep(n_requests: int = HEDGE_REQUESTS,
                    window_ms: float = HEDGE_WINDOW_MS,
                    hedge_after_ms: float = HEDGE_AFTER_MS,
                    straggler_ms: float = HEDGE_STRAGGLER_MS,
                    rate_per_ms: float = HEDGE_RATE_PER_MS):
    """Open-loop read arrivals against a STRAGGLER topology: the nearest
    replica (edge) is overloaded (``straggler_ms`` of compute per request)
    while the second-nearest (edge2) is fast.  Two identical runs through
    the router's batched path — windowed hedging off vs on — driven pump-
    by-deadline exactly like the wall-clock serving loop.  The acceptance
    check: hedged p99 <= unhedged p99."""
    import math as _math
    from repro.core import Router, percentiles
    rows = []
    for hedged in (False, True):
        cluster = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                          net=paper_topology(), measure_compute=False)
        cluster.deploy(get_function("fig4_read"), ["edge", "edge2"])
        cluster.deploy(get_function("fig4_write"), ["edge", "edge2"])
        x = _seed_and_warm(cluster, ["edge", "edge2"])
        cluster.set_compute_ms("edge", "fig4_read", straggler_ms)
        cluster.engine.configure(window_ms=window_ms)
        router = Router(cluster,
                        hedge_after_ms=hedge_after_ms if hedged else None)
        for i in range(n_requests):
            router.submit("fig4_read", x, t_send=i / rate_per_ms)
        out = {}
        while len(out) < n_requests:
            nd = router.next_deadline()
            if nd is None:
                out.update(router.pump(_math.inf))
                break
            out.update(router.pump(nd))
        # hedge winners come re-stamped against the primary's send instant,
        # so response_ms is the client-observed latency for every ticket
        pct = percentiles([r.response_ms for r in out.values()])
        rows.append({"hedged": hedged, "window_ms": window_ms,
                     "hedge_after_ms": hedge_after_ms if hedged else None,
                     "straggler_ms": straggler_ms,
                     "p50_ms": round(pct[50], 2), "p90_ms": round(pct[90], 2),
                     "p99_ms": round(pct[99], 2),
                     "hedges_fired": router.stats.hedges_fired,
                     "hedge_wins": router.stats.hedge_wins})
    return rows


# ---------------------------------------------------------------------------
# Straggler sweep: per-frame dataflow scheduler vs the wave barrier (PR 7)
# ---------------------------------------------------------------------------

STRAGGLER_NODES = ["edge", "edge2", "edge3"]
STRAGGLER_ROUNDS = 16
STRAGGLER_PER_NODE = 4
STRAGGLER_SLEEP_MS = 25.0       # WALL-clock stall injected at edge3's
                                # batched handler (set_compute_ms only
                                # charges virtual time — useless here)


@enoki_function(name="fig4_dfs", keygroups=[], codec_width=BATCH_ITEM_WIDTH)
def fig4_dfs(kv, x):
    """Stateless leaf: its store key is the serving node itself, so the
    three nodes' windows ride three independent dispatch lanes."""
    return x[:1]


def run_straggler_sweep(rounds: int = STRAGGLER_ROUNDS,
                        per_node: int = STRAGGLER_PER_NODE,
                        sleep_ms: float = STRAGGLER_SLEEP_MS):
    """WALL-clock frame-completion latency on a 3-store-node topology where
    ONE store node (edge3) is wall-clock slow, wave barrier on vs off.

    Each round submits ``per_node`` requests per node (one window per
    lane) and pumps one flush cycle; a frame's completion instant is its
    ``on_ready`` stamp (dataflow run) or the pump return (barrier run,
    where nothing streams).  With the barrier the fast nodes' frames all
    wait for edge3's sleep; with the per-frame scheduler they deliver as
    soon as their own lane finishes.  The acceptance check: fast-node p99
    improves >= 1.5x with the barrier retired."""
    from repro.core import percentiles
    rows = []
    for barrier in (True, False):
        cluster = Cluster({n: "edge" for n in STRAGGLER_NODES},
                          measure_compute=False)
        cluster.deploy(get_function("fig4_dfs"), STRAGGLER_NODES)
        x = np.ones((BATCH_ITEM_WIDTH,), np.float32)
        for nd in STRAGGLER_NODES:      # warm each lane's jit bucket
            cluster.invoke_batch("fig4_dfs", nd, [x] * per_node)
        eng = cluster.engine
        eng.configure(window_ms=4.0)
        eng.use_workers(4)
        eng.min_parallel_requests = 1
        eng.wave_barrier = barrier
        node_obj = cluster.nodes["edge3"]
        orig = node_obj.batched_handlers["fig4_dfs"]

        def slow(*a, __orig=orig, **kw):
            time.sleep(sleep_ms / 1e3)
            return __orig(*a, **kw)

        node_obj.batched_handlers["fig4_dfs"] = slow
        stamps = {}
        eng.on_ready = lambda res: stamps.update(
            dict.fromkeys(res, time.perf_counter()))
        fast_ms, slow_ms = [], []
        for r in range(rounds):
            base = float(r) * 1_000.0   # one virtual second per round
            tks = {n: [eng.submit("fig4_dfs", n, x, t_send=base + float(i))
                       for i in range(per_node)] for n in STRAGGLER_NODES}
            t0 = time.perf_counter()
            out = eng.pump(base + 999.0)
            t_end = time.perf_counter()
            for n, tickets in tks.items():
                bucket = slow_ms if n == "edge3" else fast_ms
                for t in tickets:
                    assert (t in out) != (t in stamps), (barrier, n)
                    bucket.append((stamps.get(t, t_end) - t0) * 1e3)
        pf, ps = percentiles(fast_ms), percentiles(slow_ms)
        rows.append({"wave_barrier": barrier, "sleep_ms": sleep_ms,
                     "rounds": rounds, "per_node": per_node,
                     "fast_p50_ms": round(pf[50], 2),
                     "fast_p99_ms": round(pf[99], 2),
                     "slow_p99_ms": round(ps[99], 2)})
    rows[1]["p99_improvement_x"] = round(
        rows[0]["fast_p99_ms"] / max(rows[1]["fast_p99_ms"], 1e-9), 2)
    return rows


# ---------------------------------------------------------------------------
# Parallel-pump sweep: the executor-per-store-node dispatch pipeline
# ---------------------------------------------------------------------------

PARALLEL_WORKERS = [1, 4]
PARALLEL_WINDOW_MS = 32.0       # at 2 req/ms split over 2 nodes: 64-deep
                                # windows, bucket-exact
PARALLEL_REQUESTS = 512
PAR_ITEM_WIDTH = 1024           # wide enough that a dispatch is real XLA
                                # work (the pipeline overlaps compute, not
                                # Python bookkeeping)


@enoki_function(name="fig4_par_read", keygroups=["fig4parkg"],
                codec_width=PAR_ITEM_WIDTH)
def fig4_par_read(kv, x):
    val, found = kv.get("item")
    return val[:1] + x[:1]


@enoki_function(name="fig4_par_write", keygroups=["fig4parkg"],
                codec_width=PAR_ITEM_WIDTH)
def fig4_par_write(kv, x):
    cur, _ = kv.get("item")
    kv.set("item", cur + x)
    return x[:1]


def run_parallel_sweep(window_ms: float = PARALLEL_WINDOW_MS,
                       workers=tuple(PARALLEL_WORKERS),
                       n_requests: int = PARALLEL_REQUESTS,
                       rate_per_ms: float = 2.0):
    """Serial vs parallel dispatch pipeline on a 2-STORE-NODE topology,
    measured in ONE process so every row shares the same host load (this
    host's run-to-run noise swamps cross-process comparisons):

    * ``kind=pump`` — a fixed-rate arrival stream round-robin over both
      store nodes, drained cycle-by-cycle, engine ``workers`` 1 vs N.
      For the read op the rows also record ``matches_serial``: the
      parallel pump must return the IDENTICAL ticket→result map as the
      serial one (the determinism contract).
    * ``kind=serve`` — the wall-clock serving loop, closed loop with 8
      client threads split between a read function served at ``edge`` and
      a write function served at ``edge2`` (two store nodes per flush
      cycle), ``FaasServer(workers=...)`` 1 vs N.  The acceptance check
      is the parallel row sustaining >= the serial row's ops/s.
    """
    import threading as _threading
    from repro.core import percentiles
    from repro.core.engine import BatchedInvocationEngine
    from repro.launch.faas_server import FaasServer
    cluster = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                      net=paper_topology(), measure_compute=False)
    nodes = ["edge", "edge2"]
    # read served at edge, write at edge2: every flush cycle spans two
    # store nodes (the replicated keygroup lives at both)
    cluster.deploy(get_function("fig4_par_read"), ["edge", "edge2"])
    cluster.deploy(get_function("fig4_par_write"), ["edge2"])
    x = np.ones((PAR_ITEM_WIDTH,), np.float32)
    for fn_name, nd in (("fig4_par_read", "edge"),
                        ("fig4_par_read", "edge2"),
                        ("fig4_par_write", "edge2")):
        for b in (1, 8, 64, 256):       # warm the buckets the sweep hits
            cluster.invoke_batch(fn_name, nd, [x] * b)
    for i in range(4):                  # warm the merge jit shapes too
        cluster.invoke("fig4_par_write", "edge2", x, t_send=float(i))
    cluster.flush_replication()

    def block():
        for nd in nodes:
            jax.block_until_ready(cluster.nodes[nd].stores["fig4parkg"])

    rows = []
    spacing = 1.0 / (rate_per_ms * len(nodes))   # global inter-arrival (ms)
    stream = [("fig4_par_read", "edge") if i % 2 == 0
              else ("fig4_par_write", "edge2") for i in range(n_requests)]
    # warmup + interleaved repeats + median-of-K (benchmarks.common): the
    # un-recorded warmup round absorbs residual jit/allocator transients,
    # the interleaving makes drifting host load hit serial and parallel
    # equally, and the median shrugs off one descheduled run
    def pump_pass(k):
        def run_once() -> int:
            cluster.flush_replication()
            block()
            eng = BatchedInvocationEngine(cluster, window_ms=window_ms,
                                          workers=k)
            cluster.engine = eng
            for i, (fn_name, nd) in enumerate(stream):
                eng.submit(fn_name, nd, x, t_send=i * spacing)
            out = eng.pump()    # ONE cycle: both store nodes' windows
            block()
            eng.close()
            assert len(out) == n_requests
            return n_requests
        return run_once

    samples = interleaved_repeats({k: pump_pass(k) for k in workers},
                                  repeats=3, warmup=1)
    medians = median_ops(samples)
    for k in workers:
        rows.append({"kind": "pump", "op": "read+write", "workers": k,
                     "window_ms": window_ms,
                     "ops_per_s": round(medians[k], 1),
                     "runs": [round(s, 1) for s in samples[k]]})

    # determinism check on a read-only stream spanning BOTH store nodes
    # (so the workers>1 run actually exercises the pool — a single store
    # key would fall back to the inline path and prove nothing); reads
    # leave no state behind, so both runs see identical stores
    ref_map = None
    for k in workers:
        cluster.flush_replication()
        block()
        eng = BatchedInvocationEngine(cluster, window_ms=window_ms,
                                      workers=k)
        cluster.engine = eng
        for i in range(n_requests):
            eng.submit("fig4_par_read", nodes[i % 2], x,
                       t_send=i * spacing,
                       client=("client", "client2")[i % 2])
        out = eng.pump()
        eng.close()
        m = {t: (np.asarray(r.output).tobytes(), r.t_received,
                 r.t_applied, r.node) for t, r in out.items()}
        if ref_map is None:
            ref_map = m
        else:
            rows.append({"kind": "pump", "op": "read", "workers": k,
                         "window_ms": window_ms,
                         "matches_serial": bool(m == ref_map)})

    # the wall-clock serving loop under the same host load: 32 closed-loop
    # clients, half reading (served at edge), half writing (at edge2) —
    # interleaved repeats and medians, like the pump rows
    serve_clients = 32
    serve_n = min(n_requests, 256)
    serve_p99 = {k: [] for k in workers}

    def serve_pass(k):
        def run_once() -> int:
            cluster.engine = BatchedInvocationEngine(cluster)
            errors, lat = [], []

            def client(cid, srv):
                fn = ("fig4_par_read", "fig4_par_write")[cid % 2]
                try:
                    for _ in range(serve_n // serve_clients):
                        lat.append(srv.submit(fn, x).result(
                            timeout=60.0).response_ms)
                except BaseException as e:
                    errors.append(e)

            with FaasServer(cluster, window_ms=8.0, time_scale=50.0,
                            workers=k) as srv:
                threads = [_threading.Thread(target=client,
                                             args=(cid, srv))
                           for cid in range(serve_clients)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            assert not errors, errors[0]
            serve_p99[k].append(percentiles(lat)[99])
            cluster.engine.close()
            return srv.stats.served
        return run_once

    # p99 side-channel gathers one extra (warmup) sample per variant; slice
    # the recorded tail so the reported p99 matches the recorded rounds
    serve_samples = interleaved_repeats(
        {k: serve_pass(k) for k in workers}, repeats=3, warmup=1)
    serve_medians = median_ops(serve_samples)
    for k in workers:
        rows.append({"kind": "serve", "op": "read+write", "workers": k,
                     "window_ms": 8.0,
                     "ops_per_s": round(serve_medians[k], 1),
                     "runs": [round(s, 1) for s in serve_samples[k]],
                     "p99_ms": round(float(np.median(serve_p99[k][1:])), 2)})
    return rows


# ---------------------------------------------------------------------------
# Serving sweep: the wall-clock server, open- and closed-loop arrivals
# ---------------------------------------------------------------------------

SERVE_REQUESTS = 128
SERVE_TIME_SCALE = 50.0         # 50 virtual ms per wall ms (compresses the
                                # emulated network for benchmark runtime)


def run_serving_sweep(n_requests: int = SERVE_REQUESTS,
                      window_ms: float = 8.0,
                      time_scale: float = SERVE_TIME_SCALE):
    """Drive the REAL wall-clock serving loop (launch/faas_server.py):
    open-loop (fixed wall arrival rate) and closed-loop (4 client threads,
    next request on completion) — virtual-latency percentiles + wall ops/s."""
    from repro.core import percentiles
    from repro.launch.faas_server import (FaasServer, serve_closed_loop,
                                          serve_open_loop)
    rows = []
    for mode in ("open", "closed"):
        cluster = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                          net=paper_topology(), measure_compute=False)
        cluster.deploy(get_function("fig4_read"), ["edge", "edge2"])
        cluster.deploy(get_function("fig4_write"), ["edge", "edge2"])
        x = _seed_and_warm(cluster, ["edge", "edge2"])
        t0 = time.perf_counter()
        with FaasServer(cluster, window_ms=window_ms,
                        time_scale=time_scale) as srv:
            if mode == "open":
                outs = serve_open_loop(srv, "fig4_read", lambda i: x,
                                       n_requests=n_requests,
                                       rate_per_ms=1.0)
            else:
                outs = serve_closed_loop(srv, "fig4_read", lambda i: x,
                                         n_requests=n_requests,
                                         concurrency=4)
            elapsed = time.perf_counter() - t0
            pct = percentiles([r.response_ms for r in outs])
            rows.append({"mode": mode, "window_ms": window_ms,
                         "requests": srv.stats.served,
                         "wall_ops_per_s": round(n_requests / elapsed, 1),
                         "p50_ms": round(pct[50], 2),
                         "p90_ms": round(pct[90], 2),
                         "p99_ms": round(pct[99], 2),
                         "pumps": srv.stats.pumps})
    return rows


def run():
    return {"size_sweep": run_size_sweep(),
            "batch_sweep": run_batch_sweep(),
            "window_sweep": run_window_sweep(),
            "hedge_sweep": run_hedge_sweep(),
            "straggler_sweep": run_straggler_sweep(),
            "serving_sweep": run_serving_sweep(),
            "parallel_sweep": run_parallel_sweep()}


def main(json_out: str = None):
    from benchmarks.common import print_table
    results = run()
    print_table(results["size_sweep"],
                "Fig 4 — read/write throughput vs item size")
    ceiling = [r for r in results["size_sweep"]
               if r["op"] == "read" and r["store"] == "cloud"
               and r["size_B"] >= 100_000]
    print(f"\ncloud read ceiling at >=100kB: "
          f"{[r['MB_per_s'] for r in ceiling]} MB/s (paper: 12.5 MB/s)")
    print_table(results["batch_sweep"],
                "Fig 4b — batched invocation engine ops/s vs batch size")
    for op in ("read", "write"):
        by_batch = {r["batch"]: r for r in results["batch_sweep"]
                    if r["op"] == op}
        if 64 in by_batch and 1 in by_batch:
            speedup = (by_batch[64]["ops_per_s"]
                       / by_batch[1]["ops_per_s"])
            print(f"{op}: batch-64 speedup vs batch-1 = {speedup:.1f}x")
    print_table(results["window_sweep"],
                "Fig 4c — background flusher ops/s, window_ms × nodes")
    print_table(results["hedge_sweep"],
                "Fig 4d — windowed hedging on the straggler topology")
    hs = {r["hedged"]: r for r in results["hedge_sweep"]}
    if True in hs and False in hs:
        print(f"read p99 straggler topology: unhedged {hs[False]['p99_ms']} ms"
              f" -> hedged {hs[True]['p99_ms']} ms "
              f"({hs[True]['hedge_wins']}/{hs[True]['hedges_fired']} "
              f"hedges won)")
    print_table(results["straggler_sweep"],
                "Fig 4g — wave barrier vs per-frame dataflow scheduler")
    ss = {r["wave_barrier"]: r for r in results["straggler_sweep"]}
    if True in ss and False in ss:
        print(f"fast-node frame p99 (wall): barrier {ss[True]['fast_p99_ms']}"
              f" ms -> dataflow {ss[False]['fast_p99_ms']} ms "
              f"({ss[False]['p99_improvement_x']}x)")
    print_table(results["serving_sweep"],
                "Fig 4e — wall-clock serving loop (open/closed arrivals)")
    print_table(results["parallel_sweep"],
                "Fig 4f — serial vs parallel dispatch pipeline")
    serve_rows = {r["workers"]: r for r in results["parallel_sweep"]
                  if r["kind"] == "serve"}
    if len(serve_rows) > 1:
        lo, hi = min(serve_rows), max(serve_rows)
        ratio = serve_rows[hi]["ops_per_s"] / serve_rows[lo]["ops_per_s"]
        print(f"serving loop: workers={hi} vs workers={lo} = {ratio:.2f}x "
              f"{'(sustained)' if ratio >= 1.0 else ''}")
    det = [r.get("matches_serial") for r in results["parallel_sweep"]
           if "matches_serial" in r]
    if det:
        print(f"parallel pump determinism vs serial: "
              f"{'OK' if all(det) else 'MISMATCH'}")
    for op in ("read", "write"):
        by_batch = {r["batch"]: r for r in results["batch_sweep"]
                    if r["op"] == op}
        # the documented check is at the 64-deep window (emergent batch 64
        # per node at 1 req/ms/node), apples-to-apples with batch-64
        target_w = 64.0 if 64.0 in WINDOW_SIZES_MS else max(WINDOW_SIZES_MS)
        two_node = [r for r in results["window_sweep"]
                    if r["op"] == op and r["nodes"] == 2
                    and r["window_ms"] == target_w]
        if 64 in by_batch and two_node:
            ratio = two_node[0]["ops_per_s"] / by_batch[64]["ops_per_s"]
            print(f"{op}: 2-node windowed (window {target_w:.0f} ms) vs "
                  f"single-node batch-64 = {ratio:.2f}x "
                  f"{'(sustained)' if ratio >= 1.0 else ''}")
    if json_out:
        with open(json_out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {json_out}")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-out", default=None)
    main(ap.parse_args().json_out)
