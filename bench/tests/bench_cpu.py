"""Drive a whole benchmark run on the CPU at a small arena: everything a
run does after ``run.py`` has found the chip (which a CPU has not)."""
import hashlib
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import deploy, drive, harness, spec, traffic  # noqa: E402

SLOTS = 2048
# the closed-loop cell: its mix and cell file are in bench/, its entry is
# not in BENCHMARK.json (PERF.md section 7)
CLOSED = "repl2.rw50.closed"


def closed_cell_root(tmp_path) -> pathlib.Path:
    """A copy of the benchmark's files whose BENCHMARK.json also lists the
    closed-loop cell (``kv1k_repl2`` under ``rw50_closed``)."""
    root = pathlib.Path(tmp_path) / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", ".out", ".dev",
                                                  "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CLOSED, "config": "kv1k_repl2",
                               "traffic": "rw50_closed", "chips": 1,
                               "why": "the closed loop at 256 clients"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(workload: str, seed: int, tmp_path, seconds: float = 1.0,
        rate: float = 100.0, dtype=None, root=ROOT, **traffic_params) -> dict:
    """One run at ``SLOTS`` records; ``traffic_params`` override the mix's
    (a closed loop's ``clients``)."""
    cell = spec.load_cell(root, workload)
    if "rate_per_s" in cell.params:
        cell.params = dict(cell.params, rate_per_s=rate)
    cell.traffic = dict(cell.traffic, **traffic_params)
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            peaks=spec.load_peaks("TPU v5 lite"),
                            t_start=time.perf_counter(),
                            out_dir=str(tmp_path), slots=SLOTS, dtype=dtype)


def broken_fold(monkeypatch, unchanged=False, half=False, alter=False):
    """Deploy every function with its batched step broken underneath the
    engine: the store and clock handed back unchanged, only the first half
    of each batch's valid requests applied, or a read's answer altered
    where it is produced."""
    import repro.core.cluster as cluster_mod
    orig = cluster_mod.compile_batched_handler

    def compile_broken(spec_, node_id, example):
        inner = orig(spec_, node_id, example)

        def bstep(store, clock, xs, valid, independent=False):
            if half:
                n = jnp.sum(valid)
                valid = valid & (jnp.arange(valid.shape[0]) < (n + 1) // 2)
            if unchanged:
                out = inner(jax.tree.map(jnp.copy, store), clock, xs, valid,
                            independent)
                return (store, clock) + tuple(out[2:])
            store, clock, ys, ops = inner(store, clock, xs, valid,
                                          independent)
            if alter and inner.read_only:
                ys = ys.at[0, 0].add(1.0)
            return store, clock, ys, ops

        bstep.__dict__.update(inner.__dict__)
        return bstep

    monkeypatch.setattr(cluster_mod, "compile_batched_handler",
                        compile_broken)


def assert_sound(result: dict) -> None:
    assert result["correct"], result["checks"]
    assert all(v["value"] == 0 for v in result["checks"].values())
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"


def _digest(*arrays) -> str:
    d = hashlib.sha256()
    for a in arrays:
        d.update(np.ascontiguousarray(a).tobytes())
    return d.hexdigest()[:16]


def digests(workload: str, seed: int, root=ROOT, requests: int = 2000):
    """What a cell's run is made of, at ``SLOTS`` records and a 10 s
    window: digests of its schedule (kinds, keys, due offsets, update ids
    and some update rows, as int64 and float32), of its fill (keys, hot
    slots, values) and of the first ``requests`` (function, input) it
    serves; and the buckets it warms."""
    cell = spec.load_cell(root, workload)
    cfg = cell.config
    dep = deploy.deploy(cfg, SLOTS, root=root)
    keys, hot = deploy.fill_layout(seed, SLOTS, dep.keys, dep.named_keys)
    s = traffic.make_schedule(cell.traffic, dep.keys, dep.width, seed, 10.0,
                              cell.params["rate_per_s"], root)
    last = max(int((s.update_id >= 0).sum()) - 1, 0)
    values = deploy.fill_values(jax.random.key(deploy.jax_seed(seed)), SLOTS,
                                dep.width, np.dtype(cfg["dtype"]))
    client = drive.Client(None, dep, s)
    served = hashlib.sha256()
    for i in range(requests):
        fn, x = client.request(i)
        x = np.asarray(x)
        served.update(fn.encode())
        served.update(x.tobytes())
        served.update(str(x.dtype).encode())
    return {"n": len(s),
            "schedule": _digest(*(np.asarray(a, np.int64) for a in (
                s.kind, s.key, s.due_ns, s.update_id)),
                *(s.row(u) for u in (0, 1023, 1024, last))),
            "fill": _digest(keys.astype(np.int64), np.asarray(hot, np.int64),
                            np.asarray(values)),
            "requests": served.hexdigest()[:16],
            "buckets": harness.needed_buckets(cell, dep, s,
                                              dep.cluster.engine.buckets)}
