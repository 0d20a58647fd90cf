"""Benchmark harness: one reproduction per paper figure + the roofline
table.  ``python -m benchmarks.run [--fast]``

fig3  moving-average latency, store edge vs cloud        (paper Fig 3)
fig4  read/write throughput vs item size                 (paper Fig 4)
fig6  three placements: latency + staleness              (paper Fig 5/6)
fig8  smart-city multi-function app                      (paper Fig 7/8)
roofline  per (arch × shape) terms from the dry-run      (§Roofline)

``python -m benchmarks.run serve`` instead drives the WALL-CLOCK serving
loop (launch/faas_server.py) for a fixed request count — real arrival
times mapped onto the engine's virtual timeline — and emits latency
percentiles (p50/p90/p99) plus hedge counters into the benchmark JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main_serve(argv):
    ap = argparse.ArgumentParser(prog="benchmarks.run serve")
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--mode", choices=("open", "closed"), default="open",
                    help="open: fixed arrival rate; closed: N looping clients")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="open-loop arrivals per VIRTUAL ms")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="closed-loop client threads")
    ap.add_argument("--window-ms", type=float, default=8.0)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--hedge-after-ms", type=float, default=None)
    ap.add_argument("--straggler-ms", type=float, default=0.0,
                    help="extra compute at the nearest replica (hedge demo)")
    ap.add_argument("--time-scale", type=float, default=50.0,
                    help="virtual ms per wall ms")
    ap.add_argument("--workers", type=int, default=None,
                    help="parallel-pump width: per-store-node executors "
                         "(default: serial pump)")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    from benchmarks.fig4_throughput import _seed_and_warm
    from repro.core import Cluster, get_function, percentiles
    from repro.core.network import paper_topology
    from repro.launch.faas_server import (FaasServer, serve_closed_loop,
                                          serve_open_loop)

    cluster = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                      net=paper_topology(), measure_compute=False)
    cluster.deploy(get_function("fig4_read"), ["edge", "edge2"])
    cluster.deploy(get_function("fig4_write"), ["edge", "edge2"])
    x = _seed_and_warm(cluster, ["edge", "edge2"])
    if args.straggler_ms:
        cluster.set_compute_ms("edge", "fig4_read", args.straggler_ms)

    t0 = time.perf_counter()
    with FaasServer(cluster, window_ms=args.window_ms,
                    max_batch=args.max_batch,
                    hedge_after_ms=args.hedge_after_ms,
                    time_scale=args.time_scale,
                    workers=args.workers) as srv:
        if args.mode == "closed":
            serve_closed_loop(srv, "fig4_read", lambda i: x,
                              n_requests=args.requests,
                              concurrency=args.concurrency,
                              timeout_s=60.0)
        else:
            serve_open_loop(srv, "fig4_read", lambda i: x,
                            n_requests=args.requests,
                            rate_per_ms=args.rate, timeout_s=60.0)
        elapsed = time.perf_counter() - t0
        pct = percentiles(srv.response_ms)
        rstats = srv.router.stats
        result = {"mode": args.mode, "requests": srv.stats.served,
                  "lost": srv.stats.lost,
                  "workers": args.workers,
                  "window_ms": args.window_ms,
                  "hedge_after_ms": args.hedge_after_ms,
                  "straggler_ms": args.straggler_ms,
                  "time_scale": args.time_scale,
                  "wall_s": round(elapsed, 3),
                  "wall_ops_per_s": round(srv.stats.served / elapsed, 1),
                  "p50_ms": round(pct[50], 2), "p90_ms": round(pct[90], 2),
                  "p99_ms": round(pct[99], 2),
                  "hedges_fired": rstats.hedges_fired,
                  "hedge_wins": rstats.hedge_wins,
                  "pumps": srv.stats.pumps, "wakeups": srv.stats.wakeups,
                  "repl_retries": cluster.stats.repl_retries,
                  "repl_dropped": cluster.stats.repl_dropped,
                  "repl_duped": cluster.stats.repl_duped,
                  "epoch_rejections": cluster.stats.epoch_rejections}
    print(f"serve [{args.mode}]: {result['requests']} requests in "
          f"{result['wall_s']}s ({result['wall_ops_per_s']} ops/s wall)")
    print(f"  latency (virtual ms): p50={result['p50_ms']} "
          f"p90={result['p90_ms']} p99={result['p99_ms']}")
    print(f"  transport: retries={result['repl_retries']} "
          f"dropped={result['repl_dropped']} duped={result['repl_duped']} "
          f"epoch_rejections={result['epoch_rejections']}")
    if args.hedge_after_ms is not None:
        print(f"  hedges: fired={result['hedges_fired']} "
              f"wins={result['hedge_wins']}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"serve": result}, f, indent=1)
        print(f"wrote {args.json_out}")
    return {"serve": result}


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "serve":
        return main_serve(sys.argv[2:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,fig4,fig6,fig8,roofline")
    ap.add_argument("--fast", action="store_true",
                    help="shorter workloads (CI)")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    which = set((args.only or "fig3,fig4,fig6,fig8,roofline").split(","))
    results = {}
    t0 = time.time()

    if "fig3" in which:
        from benchmarks import fig3_moving_average
        dur = 5.0 if args.fast else 30.0
        reps = 1 if args.fast else 3
        rows = fig3_moving_average.run(duration_s=dur, repeats=reps)
        from benchmarks.common import print_table
        print_table(rows, "Fig 3 — moving average latency (ms)")
        edge = [r["p50"] for r in rows if "edge" in r["placement"]]
        cloud = [r["p50"] for r in rows if "cloud" in r["placement"]]
        delta = sum(cloud) / len(cloud) - sum(edge) / len(edge)
        print(f"median delta cloud-edge: {delta:.1f} ms (paper: ≈200 ms)")
        results["fig3"] = {"rows": rows, "delta_ms": delta}

    if "fig4" in which:
        from benchmarks import fig4_throughput
        results["fig4"] = fig4_throughput.main()

    if "fig6" in which:
        from benchmarks import fig6_replication
        dur = 5.0 if args.fast else 20.0
        reps = 1 if args.fast else 3
        rows = fig6_replication.run(duration_s=dur, repeats=reps)
        from benchmarks.common import print_table
        print_table(rows, "Fig 6 — placement vs latency + staleness")
        results["fig6"] = {"rows": rows}

    if "fig8" in which:
        from benchmarks import fig8_smart_city
        dur = 10.0 if args.fast else 60.0
        reps = 1 if args.fast else 3
        rows = fig8_smart_city.run(duration_s=dur, repeats=reps)
        from benchmarks.common import print_table
        print_table(rows, "Fig 8 — smart-city latency (ms)")
        results["fig8"] = {"rows": rows}

    if "roofline" in which:
        from benchmarks import roofline_table
        roofline_table.main()
        results["roofline"] = "see artifacts/dryrun"

    print(f"\nbenchmarks done in {time.time()-t0:.1f}s")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1, default=str)
    return results


if __name__ == "__main__":
    main()
