"""Drive a whole benchmark run on the CPU at a small arena: everything a
run does after ``run.py`` has found the chip (which a CPU has not)."""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, spec  # noqa: E402

SLOTS = 2048


def run(workload: str, seed: int, tmp_path, seconds: float = 1.0,
        rate: float = 100.0, dtype=None, root=ROOT) -> dict:
    cell = spec.load_cell(root, workload)
    if "rate_per_s" in cell.params:
        cell.params = dict(cell.params, rate_per_s=rate)
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
