"""A run with the timed path broken underneath comes out not correct, for
each fault the cells can have: a step that hands its state back
unchanged, half of each batch left out, the exchange between replicas
left out, and an answer altered where it is produced."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_cpu  # noqa: E402


@pytest.mark.parametrize("fault", ["unchanged", "half", "alter"])
def test_broken_fold_is_caught(fault, monkeypatch, tmp_path):
    bench_cpu.broken_fold(monkeypatch, **{fault: True})
    res = bench_cpu.run("repl2.rw50", seed=23, tmp_path=tmp_path,
                        rate=200.0)
    assert res["correct"] is False, res["checks"]


def test_replication_left_out_is_caught(monkeypatch, tmp_path):
    from repro.core.cluster import Cluster
    monkeypatch.setattr(Cluster, "_schedule_replication",
                        lambda self, kg, source, t_apply: None)
    res = bench_cpu.run("repl2.rw50", seed=29, tmp_path=tmp_path)
    assert res["correct"] is False
    assert res["checks"]["arena_diff.edge2"]["value"] > 0
    assert res["checks"]["arena_diff.edge"]["value"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half", "alter"])
def test_broken_fold_is_caught_in_the_closed_loop(fault, monkeypatch,
                                                  tmp_path):
    bench_cpu.broken_fold(monkeypatch, **{fault: True})
    res = bench_cpu.run(bench_cpu.CLOSED, seed=31, tmp_path=tmp_path,
                        rate=2000.0, clients=4,
                        root=bench_cpu.closed_cell_root(tmp_path))
    assert res["correct"] is False, res["checks"]


def test_replication_left_out_is_caught_in_the_closed_loop(monkeypatch,
                                                           tmp_path):
    from repro.core.cluster import Cluster
    monkeypatch.setattr(Cluster, "_schedule_replication",
                        lambda self, kg, source, t_apply: None)
    res = bench_cpu.run(bench_cpu.CLOSED, seed=37, tmp_path=tmp_path,
                        rate=2000.0, clients=4,
                        root=bench_cpu.closed_cell_root(tmp_path))
    assert res["correct"] is False
    assert res["checks"]["arena_diff.edge2"]["value"] > 0
    assert res["checks"]["arena_diff.edge"]["value"] == 0
