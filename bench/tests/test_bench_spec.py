"""BENCHMARK.json against the benchmark's contract, and every entry
resolved to the files the harness finds by name."""
import json
import pathlib
import re
import shutil
import sys

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


def test_unknown_names_are_refused(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell(ROOT, "no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99")
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
