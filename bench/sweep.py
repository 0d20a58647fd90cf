"""Find a cell's knee: offer its open-loop mix at a list of fixed rates, one
window each, in one process on one deployment.

    python bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 100 200 400 ...

A rate is sustained when at least 99% of the requests due before the
window's last second are answered inside the window, the backlog does
not grow through the window (the median latency of the window's last
quarter is at most 1.5 times that of its first), and the tail has not
left its low-load level: the 95th percentile is at most 1.15 times that
of the lowest rate swept.  The knee is the highest sustained rate; a cell
is offered 0.8 of it (``bench/cells/<name>.json``).  Rates run in rising
order, and the sweep stops after two rates in a row that are not
sustained.  One JSON line per rate
on standard output; nothing is checked for correctness here, the cell's
own runs do that.
"""
import time

T_START = time.perf_counter()

import argparse                 # noqa: E402
import json                     # noqa: E402
import pathlib                  # noqa: E402
import sys                      # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TAIL_RISE = 1.15
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def quarter_p50(hist, t0: int, t1: int, q: int) -> float:
    import numpy as np
    span = (t1 - t0) // 4
    lo, hi = t0 + q * span, t0 + (q + 1) * span
    n = hist.issued
    due = hist.due_ns[:n]
    idx = np.flatnonzero((due >= lo) & (due < hi))
    if idx.size == 0:
        return float("nan")
    done = hist.done_ns[idx]
    lat = np.where(done >= 0, done - due[idx], 1 << 62) / 1e6
    return float(np.median(lat))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.load_cell(ROOT, args.workload)
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 3
    spec.use_compile_cache(ROOT)

    from bench import deploy, drive, harness, traffic
    from repro.launch.faas_server import FaasServer
    if cell.traffic["loop"] != "open":
        print("sweep.py: the cell's mix is not an open loop",
              file=sys.stderr)
        return 2
    open_loop = spec.load_part("loops", "open")
    cfg = cell.config
    dep = deploy.deploy(cfg, int(cfg["recordcount"]))
    deploy.fill(dep, args.seed, cfg["keygroup"]["fill_node"])
    keys = dep.keys
    top = traffic.make_schedule(cell.traffic, keys, dep.width, args.seed,
                                args.seconds, max(args.rates))
    warmed = deploy.warm(dep, harness.needed_buckets(
        cell, dep, top, dep.cluster.engine.buckets))
    harness.log(f"set-up {time.perf_counter() - T_START:.3f} s, "
                f"{warmed} warm executions")
    scfg = cfg["server"]
    missed, base_p95 = 0, None
    for i, rate in enumerate(sorted(args.rates)):
        sched = traffic.make_schedule(cell.traffic, keys, dep.width,
                                      args.seed + 1 + i, args.seconds, rate)
        with FaasServer(dep.cluster, window_ms=float(scfg["window_ms"]),
                        max_batch=scfg["max_batch"],
                        hedge_after_ms=scfg["hedge_after_ms"],
                        client=dep.client,
                        time_scale=float(scfg["time_scale"]),
                        workers=scfg["workers"]) as srv:
            client = drive.Client(srv, dep, sched)
            t0 = time.perf_counter_ns() + 20_000_000
            t1 = t0 + int(args.seconds * 1e9)
            open_loop.drive(client, t0, t1, cell.traffic)
            hist = client.hist
            harness._sleep_until(t1)
            # requests due before the window's last second, answered in it
            early = hist.due_ns < t1 - 1_000_000_000
            in_window = int(np.sum(early & (hist.done_ns >= 0)
                                   & (hist.done_ns < t1) & ~hist.failed))
            offered = int(np.sum(early))
            drive.wait_answers(hist, t1, wait_s=30.0)
        lat = np.where(hist.done_ns >= 0, hist.done_ns - hist.due_ns,
                       1 << 62) / 1e6
        q = [quarter_p50(hist, t0, t1, k) for k in range(4)]
        p95 = float(np.percentile(lat, 95))
        base_p95 = p95 if base_p95 is None else base_p95
        row = {"rate_per_s": rate, "offered": offered,
               "answered_in_window": in_window,
               "delivered_share": in_window / offered,
               "p50_ms": float(np.median(lat)),
               "p95_ms": p95,
               "send_lag_p95_ms": float(np.percentile(
                   (hist.send_ns - hist.due_ns) / 1e6, 95)),
               "quarter_p50_ms": q,
               "sustained": bool(in_window >= 0.99 * offered
                                 and q[3] <= 1.5 * q[0]
                                 and p95 <= TAIL_RISE * base_p95)}
        print(json.dumps(row), flush=True)
        missed = 0 if row["sustained"] else missed + 1
        if missed == 2:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
