"""Wall-clock serving demo: the batched engine hosted as an actual server.

Until now every engine demo drove virtual time by hand (`pump(until_t)`).
Here `FaasServer` maps REAL arrival times onto the virtual timeline:

  1. client threads submit stateful requests whenever they like; the
     serving thread sleeps exactly until the next window close
     (`router.next_deadline()`) instead of polling;
  2. a closed-loop run (each client fires its next request on completion)
     shows emergent batching under feedback;
  3. a STRAGGLER topology (the nearest replica serves slowly) shows the
     windowed hedge: read-only requests whose window outlives the hedge
     deadline are duplicated at the second replica, and the earlier
     completion wins (the duplicate goes to the lowest-latency-EWMA
     replica once samples exist);
  4. the CONCURRENT dispatch pipeline: `workers=2` runs the two store
     nodes' groups of each flush cycle on per-node executors, and an
     asyncio closed loop hosts 16 LOGICAL clients on one event loop —
     no thread per client.

Run:  PYTHONPATH=src python examples/serve_wallclock.py
"""
import asyncio
import time

import jax.numpy as jnp
import numpy as np

from repro.core import Cluster, enoki_function, get_function, percentiles
from repro.core.network import paper_topology
from repro.launch.faas_server import (FaasServer, serve_closed_loop,
                                      serve_closed_loop_async)


@enoki_function(name="wc_acc", keygroups=["wc_kg"], codec_width=16)
def wc_acc(kv, x):
    cur, found = kv.get("total")
    kv.set("total", cur + x)
    return cur[:1] + x[:1]


@enoki_function(name="wc_read", keygroups=["wc_kg"], codec_width=16)
def wc_read(kv, x):
    cur, found = kv.get("total")
    return cur[:1]


def fresh_cluster():
    cluster = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                      net=paper_topology(), measure_compute=False)
    cluster.deploy(get_function("wc_acc"), ["edge", "edge2"])
    cluster.deploy(get_function("wc_read"), ["edge", "edge2"])
    x = np.ones(16, np.float32)
    for node in ("edge", "edge2"):          # warm jit buckets + seed state
        for b in (1, 8, 64):
            cluster.invoke_batch("wc_acc", node, [x] * b)
            cluster.invoke_batch("wc_read", node, [x] * b)
    cluster.flush_replication()
    return cluster, x


def main():
    # -- 1. open-loop wall-clock serving ------------------------------------
    cluster, x = fresh_cluster()
    t0 = time.perf_counter()
    with FaasServer(cluster, window_ms=8.0, time_scale=100.0) as srv:
        futs = [srv.submit("wc_acc", x, session_id="demo")
                for _ in range(128)]
        outs = [f.result(timeout=30.0) for f in futs]
    wall = time.perf_counter() - t0
    pct = percentiles([r.response_ms for r in outs])
    print(f"open loop: {len(outs)} requests in {wall*1e3:.0f} ms wall "
          f"({len(outs)/wall:.0f} ops/s), {srv.stats.pumps} pumps")
    print(f"  virtual latency p50/p99: {pct[50]:.1f}/{pct[99]:.1f} ms "
          f"(window 8 ms)")

    # -- 2. closed loop: 8 clients, next request on completion --------------
    cluster, x = fresh_cluster()
    t0 = time.perf_counter()
    with FaasServer(cluster, window_ms=4.0, time_scale=100.0) as srv:
        rs = serve_closed_loop(srv, "wc_acc", lambda i: x,
                               n_requests=128, concurrency=8)
    wall = time.perf_counter() - t0
    print(f"closed loop: {len(rs)} requests, {srv.stats.pumps} pumps, "
          f"{len(rs)/wall:.0f} ops/s wall")

    # -- 3. windowed hedging on a straggler topology ------------------------
    for hedged in (False, True):
        cluster, x = fresh_cluster()
        cluster.set_compute_ms("edge", "wc_read", 60.0)     # straggler
        with FaasServer(cluster, window_ms=16.0, time_scale=100.0,
                        hedge_after_ms=4.0 if hedged else None) as srv:
            futs = [srv.submit("wc_read", x) for _ in range(64)]
            outs = [f.result(timeout=30.0) for f in futs]
        pct = percentiles([r.response_ms for r in outs])
        extra = (f", hedges fired/won: {srv.router.stats.hedges_fired}/"
                 f"{srv.router.stats.hedge_wins}" if hedged else "")
        print(f"straggler {'with' if hedged else 'no  '} hedge: "
              f"p50/p99 = {pct[50]:.1f}/{pct[99]:.1f} ms{extra}")

    # -- 4. parallel pump + asyncio clients: one process, many logical
    #       clients, per-store-node executors -------------------------------
    cluster, x = fresh_cluster()
    t0 = time.perf_counter()
    with FaasServer(cluster, window_ms=4.0, time_scale=100.0,
                    workers=2) as srv:
        rs = asyncio.run(serve_closed_loop_async(
            srv, "wc_acc", lambda i: x, n_requests=128, concurrency=16))
    wall = time.perf_counter() - t0
    print(f"asyncio closed loop (16 logical clients, workers=2): "
          f"{len(rs)} requests, {len(rs)/wall:.0f} ops/s wall, "
          f"{srv.stats.pumps} pumps")


if __name__ == "__main__":
    main()
