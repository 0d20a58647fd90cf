"""One run of one cell: deploy, fill, warm, measure a window, check.

``run_cell`` is everything a run does after ``run.py`` has found the chip:
it builds the cell's configuration on the system under test, fills the
records from the seed, warms the shapes the cell's traffic reaches, serves
the traffic for ``seconds`` through ``FaasServer`` (tracing the window when
asked), waits for every answer, drains replication, and holds what was
served and both arenas to the plain reference that the configuration
names (``reference``, a path in the checkout).  The result is the line
``run.py`` prints.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import json
import logging
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import deploy, drive, spec, traffic
from bench import trace as tracing
from bench.spec import Cell
from repro.analysis.jitprof import CompileCounter
from repro.launch.faas_server import FaasServer

LIMITS = 0                      # every count compared is exact


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def needed_buckets(cell: Cell, dep: deploy.Deployment,
                   sched: traffic.Schedule,
                   engine_buckets) -> Dict[str, List[int]]:
    """The engine buckets each function of the cell can reach in the
    window, as the functions' kinds list them."""
    owner = drive.route(dep, sched)
    fns = {}
    for j, (kind, entry) in enumerate(dep.functions):
        fns.update(kind.buckets(dep, entry, sched, owner == j,
                                engine_buckets))
    return fns


class _CompileNames(logging.Handler):
    """Names of the programs JAX compiles while active (its compile log,
    kept off standard error)."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split()[1])

    def __enter__(self):
        self._was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", self._was)
        return False


class _Events:
    """Counts of JAX's monitoring events while active (compile-cache hits
    and misses during set-up)."""

    def __init__(self):
        self.counts = collections.Counter()

    def __call__(self, name, *args, **kwargs):
        self.counts[name] += 1

    def __enter__(self):
        jax.monitoring.register_event_listener(self)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_listener(self)
        return False


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers from
    it (``bench/metrics/<name>.py``)."""
    cell: Cell
    seconds: float
    setup_s: float
    t0_ns: int
    t1_ns: int
    sched: traffic.Schedule
    hist: drive.History
    clocks: np.ndarray              # per request, from the check
    counters: Dict[str, float]      # program counters over the window
    trace: Optional[tracing.Summary]
    peaks: dict
    arena: dict                     # slots, width, itemsize

    def attempted(self) -> np.ndarray:
        """Indices of the requests due in the window."""
        n = self.hist.issued
        due = self.hist.due_ns[:n]
        return np.flatnonzero((due >= self.t0_ns) & (due < self.t1_ns))

    def latencies_ms(self, idx: np.ndarray) -> np.ndarray:
        """Due instant to answer on the host; a lost or failed request
        counts as beyond any limit (the longest wait a run allows)."""
        h = self.hist
        lat = (h.done_ns[idx] - h.due_ns[idx]) / 1e6
        worst = (self.seconds + drive.WAIT_AFTER_S) * 1e3
        bad = (h.done_ns[idx] < 0) | h.failed[idx]
        return np.where(bad, worst, lat)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             peaks: dict, t_start: float, out_dir: str,
             slots: Optional[int] = None, dtype=None) -> dict:
    """One run; returns the result line (as a dict).  ``slots`` and
    ``dtype`` override the configuration's record count and type (the CPU
    tests and the control use them; the benchmark's runs never do)."""
    cfg = cell.config
    slots = int(slots or cfg["recordcount"])
    ref = spec.load_module(cell.root / cfg["reference"], "the reference")
    loop = spec.load_part("loops", cell.traffic["loop"], cell.root)
    with _Events() as setup_events:
        dep = deploy.deploy(cfg, slots, dtype, cell.root)
        writer = cfg["keygroup"]["fill_node"]
        layout = deploy.fill(dep, seed, writer)
        sched = traffic.make_schedule(cell.traffic, dep.keys, dep.width,
                                      seed, seconds,
                                      cell.params["rate_per_s"], cell.root)
        eng = dep.cluster.engine
        buckets = needed_buckets(cell, dep, sched, eng.buckets)
        warmed = deploy.warm(dep, buckets)
        scfg = cfg["server"]
        srv = FaasServer(dep.cluster, window_ms=float(scfg["window_ms"]),
                         max_batch=scfg["max_batch"],
                         hedge_after_ms=scfg["hedge_after_ms"],
                         client=dep.client,
                         time_scale=float(scfg["time_scale"]),
                         workers=scfg["workers"])
        srv.start()
        client = drive.Client(srv, dep, sched)
    setup_s = time.perf_counter() - t_start
    hits = setup_events.counts["/jax/compilation_cache/cache_hits"]
    misses = setup_events.counts["/jax/compilation_cache/cache_misses"]
    log(f"set-up: {setup_s:.3f} s ({'cold' if misses else 'warm'}: "
        f"{misses} programs compiled, {hits} loaded from the compile "
        f"cache); warmed {warmed} executions at buckets {buckets}")

    before = _counters(dep, srv)
    log_dir = f"{out_dir}/trace"
    with CompileCounter() as compiles, _CompileNames() as compiled:
        t0 = time.perf_counter_ns() + 20_000_000
        t1 = t0 + int(seconds * 1e9)
        with _traced(trace, log_dir), \
                jax.profiler.TraceAnnotation(tracing.WINDOW_ANNOTATION):
            try:
                loop.drive(client, t0, t1, cell.traffic)
            except BaseException:
                srv.stop(drain=False)
                raise
            _sleep_until(t1)
        in_window = compiles.events
    log(f"compiles inside the window: {in_window} {compiled.names}")
    hist = client.hist
    drive.wait_answers(hist, t1)
    srv.stop()
    dep.cluster.flush_replication()
    after = _counters(dep, srv)
    counters = {k: after[k] - before[k] for k in after}
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"server: served={srv.stats.served} lost={srv.stats.lost} "
        f"cycle_errors={srv.stats.cycle_errors}; engine errors: "
        f"{[repr(e) for e in eng.errors]}")
    log(f"merges: dispatches={dep.cluster.stats.merge_dispatches} "
        f"snapshots={dep.cluster.stats.merge_snapshots} "
        f"aligned={dep.cluster.stats.merge_aligned} "
        f"fallback={dep.cluster.stats.merge_fallback}")

    # the program's state comes to the host, then goes; the reference runs
    # on the host alone
    arenas = {}
    for node in dep.replicas:
        nd = dep.cluster.nodes[node]
        with nd.lock:
            arenas[node] = dict(zip(ref.LEAVES,
                                    jax.device_get(nd.stores[dep.kg])))
    writer_id = dep.cluster.nodes[writer].node_id
    update_writer = _update_writer(dep, client, sched)
    if update_writer is None:
        update_writer = writer_id
    arena_info = {"slots": slots, "width": dep.width,
                  "itemsize": int(np.dtype(dep.dtype).itemsize)}
    del dep, srv, eng, client
    gc.collect()
    fill = ref.Fill(
        keys=layout[0], hot_slots=layout[1], writer_id=writer_id,
        values=np.asarray(jax.device_get(deploy.fill_values(
            jax.random.key(deploy.jax_seed(seed)), slots,
            arena_info["width"], np.dtype(cfg["dtype"])))))
    n = hist.issued
    obs = ref.Observed(
        kind=sched.kind[:n], key=sched.key[:n],
        update_id=sched.update_id[:n], ticket=hist.ticket[:n],
        send_ns=hist.send_ns[:n], done_ns=hist.done_ns[:n],
        failed=hist.failed[:n], outputs=hist.outputs[:n])
    checks, clocks = ref.check(obs, fill, sched.row, update_writer, arenas)
    del arenas

    summary = None
    if trace:
        summary = tracing.summarize(tracing.load(tracing.find_xplane(log_dir)))
    run = Run(cell=cell, seconds=seconds, setup_s=setup_s, t0_ns=t0,
              t1_ns=t1, sched=sched, hist=hist, clocks=clocks,
              counters=counters, trace=summary, peaks=peaks,
              arena=arena_info)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.reader.read(run)
        if v is None:
            log(f"metric {m.name}: listed for this cell, but its reader "
                f"found nothing to read in this run")
        else:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    attempted = run.attempted()
    result = {
        "correct": all(v <= LIMITS for v in checks.values()),
        "attempted": int(attempted.size),
        "failed": int(checks["lost"]),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(mem)},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": v, "limit": LIMITS}
                        for k, v in checks.items()}
    return result


def _update_writer(dep, client, sched) -> Optional[int]:
    """Node id of the function that serves the schedule's first update
    (None where the schedule updates nothing)."""
    upd = np.flatnonzero(sched.kind == traffic.UPDATE)
    if upd.size == 0:
        return None
    fn, _ = client.request(int(upd[0]))
    return dep.cluster.nodes[dep.fn_nodes[fn][0]].node_id


def _counters(dep, srv) -> Dict[str, float]:
    es, cs = dep.cluster.engine.stats, dep.cluster.stats
    return {"requests_flushed": es.requests_flushed,
            "dispatches": es.dispatches, "cycles": es.cycles,
            "replication_bytes": dep.cluster.replication_bytes,
            "merge_dispatches": cs.merge_dispatches,
            "merge_snapshots": cs.merge_snapshots,
            "served": srv.stats.served}


@contextlib.contextmanager
def _traced(on: bool, log_dir: str):
    if not on:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _sleep_until(t_ns: int) -> None:
    delay = t_ns - time.perf_counter_ns()
    if delay > 0:
        time.sleep(delay / 1e9)


def report(result: dict) -> None:
    """The compared numbers on stderr, last, then the result line."""
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
