"""``BENCHMARK.json`` and the files it names, found by name.

A workload names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``), and has a cell file of its
own (``bench/cells/<workload>.json``, its offered rate).  Every metric, end
to end or per layer, is read by ``bench/metrics/<metric>.py``; which cells
report it, its unit and what it moves are said in ``BENCHMARK.json`` alone.
A configuration's functions are deployed by ``bench/kinds/<kind>.py``, and
it names its plain reference by path; a mix's key distribution is
``bench/dists/<requestdistribution>.py`` (``uniform`` where the mix names
none) and its client loop ``bench/loops/<loop>.py``.  Adding any of them
is adding files and entries; no file here names a cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """BENCHMARK.json names something the benchmark's files do not hold."""


def load_benchmark(root: pathlib.Path) -> dict:
    path = pathlib.Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    return json.loads(path.read_text())


def metric_applies(metric: dict, workload: str,
                   end_to_end: Dict[str, dict]) -> bool:
    """Whether a cell reports ``metric``: the cells its ``workloads`` list
    names, else every cell that reports the end-to-end metric it moves
    (for an end-to-end metric without the list: every cell)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    moved = metric.get("moves")
    if moved is None:
        return True
    return metric_applies(end_to_end[moved], workload, end_to_end)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str]            # None for an end-to-end metric
    moves: Optional[str]
    reader: object                  # module with read(run)


def load_module(path: pathlib.Path, what: str):
    """The Python file at ``path``, executed afresh as a module of its
    own (listed in ``sys.modules``, which dataclasses look up)."""
    path = pathlib.Path(path)
    if not path.is_file():
        raise SpecError(f"no {path} for {what}")
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "__")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_part(group: str, name: str, root: pathlib.Path = ROOT):
    """``bench/<group>/<name>.py``: a metric's reader, a function kind, a
    key distribution or a client loop, found by its name."""
    return load_module(pathlib.Path(root) / "bench" / group / f"{name}.py",
                       f"{group[:-1]} {name!r}")


def load_reader(name: str, root: pathlib.Path = ROOT):
    return load_part("metrics", name, root)


@dataclasses.dataclass
class Cell:
    name: str
    root: pathlib.Path              # the checkout its files were found in
    chips: int
    config: dict
    traffic: dict
    params: dict                    # the cell file's
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names no listed config")
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    cell_file = root / "bench" / "cells" / f"{name}.json"
    params = _json(cell_file)
    if "rate_per_s" not in params:
        raise SpecError(f"cell {name!r} has no rate_per_s in {cell_file}")
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def metrics(entries, per_layer):
        return [Metric(name=m["name"], unit=m["unit"], better=m["better"],
                       source=m["source"],
                       layer=m["layer"] if per_layer else None,
                       moves=m["moves"] if per_layer else None,
                       reader=load_reader(m["name"], root))
                for m in entries if metric_applies(m, name, e2e)]

    return Cell(name=name, root=root, chips=int(w["chips"]), config=config,
                traffic=traffic, params=params,
                end_to_end=metrics(bench["end_to_end"], False),
                per_layer=metrics(bench["per_layer"], True))


def use_compile_cache(root: pathlib.Path = ROOT) -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    every program kept, none evicted: eviction reads an access-time file
    beside each entry, and an entry written without one (by a process that
    did not evict) then fails every later write."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(pathlib.Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def load_peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The chip's peaks; a device missing from the table is an error."""
    table = json.loads((pathlib.Path(root) / "bench" / "peaks.json")
                       .read_text())
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json")
    return table[device_kind]
