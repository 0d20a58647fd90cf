"""Every cell through the harness's own functions at 2,048 slots on the
CPU: every served output and every replica's arena equal to the
sequential reference, and every end-to-end metric reported."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_cpu  # noqa: E402


@pytest.mark.parametrize("workload,replicas", [
    ("repl2.rw50", ["edge", "edge2"]),
    ("single.rw50", ["edge2"]),
    ("repl2.r100", ["edge", "edge2"]),
    (bench_cpu.CLOSED, ["edge", "edge2"]),
])
def test_open_loop_cell_agrees_with_reference(workload, replicas, tmp_path):
    """Each cell under its own loop: the open loop offers 100 requests in
    the window; the closed loop keeps 4 clients busy through it, under a
    ceiling of 2,000, and reports no latency of its own."""
    closed = workload == bench_cpu.CLOSED
    if closed:
        res = bench_cpu.run(workload, seed=2**31 + 5, tmp_path=tmp_path,
                            rate=2000.0, clients=4,
                            root=bench_cpu.closed_cell_root(tmp_path))
    else:
        res = bench_cpu.run(workload, seed=2**31 + 5, tmp_path=tmp_path)
    bench_cpu.assert_sound(res)
    assert sorted(k.split(".", 1)[1] for k in res["checks"]
                  if k.startswith("arena_diff")) == replicas
    m = res["metrics"]
    if closed:
        assert set(m) == {"ops_per_s", "setup_s"}
        assert 4 < res["attempted"] < 2000
        assert m["ops_per_s"]["value"] > 0
        return
    assert res["attempted"] == 100
    assert {"ops_per_s", "p50_ms", "p95_ms", "setup_s"} <= set(m)
    assert ("stale_p95_ms" in m) == (workload == "repl2.rw50")
    assert 0 < m["p50_ms"]["value"] <= m["p95_ms"]["value"]


def test_a_closed_loop_that_uses_up_its_schedule_fails(tmp_path):
    """The schedule is the closed loop's ceiling: a run whose clients get
    through it before the window closes fails instead of idling."""
    with pytest.raises(RuntimeError, match="used up its schedule"):
        bench_cpu.run(bench_cpu.CLOSED, seed=41, tmp_path=tmp_path,
                      rate=10.0, clients=4,
                      root=bench_cpu.closed_cell_root(tmp_path))
