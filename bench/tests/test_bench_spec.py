"""BENCHMARK.json against the benchmark's contract, and every entry
resolved to the files the harness finds by name."""
import json
import pathlib
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_entries_have_exactly_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_units_and_lines():
    everything = (BENCH["configs"] + BENCH["workloads"]
                  + BENCH["end_to_end"] + BENCH["per_layer"])
    texts = [e[k] for e in everything for k in ("why", "layer") if k in e]
    texts += [c["source"] for c in BENCH["configs"]]
    for e in everything:
        assert NAME.match(e["name"]), e["name"]
    for text in texts:
        assert 1 <= len(text) <= 200 and not set(text) & {"\n", "\t"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_end_to_end_bounds_and_sources():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_by_name(workload):
    """Config, traffic, cell file and every metric's reader are found from
    the names alone; each per-layer metric moves an end-to-end metric the
    cell reports."""
    cell = spec.load_cell(ROOT, workload)
    assert cell.chips == 1
    assert cell.params["rate_per_s"] > 0
    names = [m.name for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.reader.read)
    for m in cell.per_layer:
        assert m.moves in names, (m.name, m.moves)


def test_every_config_is_used_and_every_metric_has_a_reader():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert (ROOT / cfg["reference"]).is_file()
    readers = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["name"] in readers
        for w in m.get("workloads", []):
            assert w in WORKLOADS


def test_new_cell_config_traffic_and_metric_are_files_and_entries(tmp_path):
    """A later change adds a deployment, a mix, a cell and a per-layer
    metric by new files and new entries alone: the harness finds them by
    name with no existing file edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", ".out", ".dev",
                                                  "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/kv1k_repl2.json").read_text())
    cfg["name"] = "kv1k_repl2_b"
    (tmp_path / "bench/configs/kv1k_repl2_b.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/rw50_open.json").read_text())
    mix["readproportion"], mix["updateproportion"] = 0.95, 0.05
    (tmp_path / "bench/traffic/rw95_open.json").write_text(json.dumps(mix))
    (tmp_path / "bench/cells/repl2b.rw95.json").write_text(
        json.dumps({"rate_per_s": 100}))
    (tmp_path / "bench/metrics/served_share.py").write_text(
        'def read(run):\n    return 100.0\n')
    bench["configs"].append(dict(bench["configs"][0], name="kv1k_repl2_b",
                                 file="bench/configs/kv1k_repl2_b.json"))
    bench["workloads"].append({"name": "repl2b.rw95",
                               "config": "kv1k_repl2_b",
                               "traffic": "rw95_open", "chips": 1,
                               "why": "a mix added by files alone"})
    for m in bench["end_to_end"]:
        if m["name"] in ("p50_ms", "p95_ms"):
            m["workloads"].append("repl2b.rw95")
    bench["per_layer"].append({"name": "served_share", "unit": "%",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine", "moves": "p95_ms",
                               "workloads": ["repl2b.rw95"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(tmp_path, "repl2b.rw95")
    assert cell.config["name"] == "kv1k_repl2_b"
    assert cell.traffic["readproportion"] == 0.95
    assert cell.params == {"rate_per_s": 100}
    assert [m.name for m in cell.per_layer] == ["served_share"]
    assert cell.per_layer[0].reader.read(None) == 100.0
    # the cells already there resolve as before
    assert spec.load_cell(tmp_path, "repl2.rw50").per_layer


TOY_KIND = '''"""Reads the record whose hash the request carries, negated."""
import numpy as np

from bench.traffic import READ
from repro.core import enoki_function
from repro.core.store import kv_get


def deploy(dep, entry):
    def handler(kv, x):
        row, _, _, _ = kv_get(kv.state[0], x[0])
        return -row.astype(np.float32)
    enoki_function(name=entry["name"], keygroups=[dep.kg],
                   codec_width=dep.width)(handler)
    dep.place(entry["name"], entry["nodes"], np.zeros((1,), np.int32))


def serves(dep, entry, kind, key):
    return kind == READ


def request(dep, entry, sched, i):
    return entry["name"], dep.key_hashes[sched.key[i]:sched.key[i] + 1]


def buckets(dep, entry, sched, mine, engine_buckets):
    return {entry["name"]: list(engine_buckets)} if mine.any() else {}
'''

TOY_DIST = '''"""Keys skewed 1/(rank + 1), a block from a stream of its own."""
import numpy as np

from bench.traffic import READ, UPDATE


def block(traffic, keys, index):
    n = int(traffic["block"])
    n_read = int(round(n * float(traffic["readproportion"])))
    p = 1.0 / np.arange(1, keys + 1)
    key = np.random.default_rng([index, 17]).choice(keys, n, p=p / p.sum())
    kind = np.where(np.arange(n) < n_read, READ, UPDATE)
    return np.stack([kind, key], axis=1).astype(np.int64)
'''

TOY_LOOP = '''"""Requests evenly spaced over the window, each sent when due."""
import time

import numpy as np


def due_ns(traffic, rng, n, seconds):
    return (np.arange(n) * (seconds * 1e9 / n)).astype(np.int64)


def drive(client, t0_ns, t1_ns, traffic):
    client.hist.due_ns[:] = t0_ns + client.sched.due_ns
    for i in range(len(client.sched)):
        delay = client.hist.due_ns[i] - time.perf_counter_ns()
        if delay > 0:
            time.sleep(delay / 1e9)
        client.send(i)
'''

TOY_REFERENCE = '''"""The plain reference, for reads that come back negated."""
import numpy as np

from bench.reference import LEAVES, Fill, Observed  # noqa: F401
from bench.reference import check as plain_check


def check(obs, fill, rows, writer_id, arenas):
    outputs = [-np.asarray(o) if k == 0 else o
               for k, o in zip(obs.kind, obs.outputs)]
    obs = Observed(**dict(vars(obs), outputs=outputs))
    return plain_check(obs, fill, rows, writer_id, arenas)
'''


def test_new_kind_distribution_loop_and_reference_are_files(tmp_path):
    """A later change brings a deployment's own function kind, key
    distribution, client loop and reference as new files, edits none, and
    its cell runs correct: reads of request-carried hashes over all 2,048
    records, keys skewed over all of them, evenly paced, judged by a
    reference that the new configuration names.  The cells already there
    keep what they are made of."""
    sys.path.insert(0, str(ROOT / "bench" / "tests"))
    import bench_cpu
    import test_bench_invariant
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", ".out", ".dev",
                                                  "__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    files = {"kinds/hash_read.py": TOY_KIND, "dists/skew.py": TOY_DIST,
             "loops/paced.py": TOY_LOOP, "toy_reference.py": TOY_REFERENCE,
             "cells/toy.skew.json": json.dumps({"rate_per_s": 100})}
    cfg = json.loads((ROOT / "bench/configs/kv1k_single.json").read_text())
    cfg.update(name="toy", recordcount=2048, requestkeys=2048,
               record_floats=16, reference="bench/toy_reference.py",
               functions=[{"name": "toy_read", "kind": "hash_read",
                           "nodes": ["edge2"]}])
    files["configs/toy.json"] = json.dumps(cfg)
    mix = json.loads((ROOT / "bench/traffic/r100_open.json").read_text())
    mix.update(name="skew_paced", loop="paced", requestdistribution="skew")
    files["traffic/skew_paced.json"] = json.dumps(mix)
    for name, text in files.items():
        assert not (tmp_path / "bench" / name).exists()
        (tmp_path / "bench" / name).write_text(text)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][1], name="toy",
                                 file="bench/configs/toy.json"))
    bench["workloads"].append({"name": "toy.skew", "config": "toy",
                               "traffic": "skew_paced", "chips": 1,
                               "why": "a deployment added by files alone"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    from bench import traffic
    keys = traffic.block_ops(mix, 2048, 3, root=tmp_path)[:, 1]
    assert np.bincount(keys).argmax() == 0 and keys.max() > 1024
    res = bench_cpu.run("toy.skew", seed=2**33 + 3, tmp_path=tmp_path / "out",
                        root=tmp_path)
    bench_cpu.assert_sound(res)
    assert res["attempted"] == 100
    assert set(res["checks"]) == {"lost", "update_bad", "read_unwritten",
                                  "read_regress", "arena_diff.edge2"}
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert {k: after[k] for k in before} == before
    test_bench_invariant.check_digests("repl2.rw50", 1, root=tmp_path)


def test_unknown_names_are_refused(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell(ROOT, "no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99")
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
