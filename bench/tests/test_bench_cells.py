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
])
def test_open_loop_cell_agrees_with_reference(workload, replicas, tmp_path):
    res = bench_cpu.run(workload, seed=2**31 + 5, tmp_path=tmp_path)
    bench_cpu.assert_sound(res)
    assert res["attempted"] == 100
    assert sorted(k.split(".", 1)[1] for k in res["checks"]
                  if k.startswith("arena_diff")) == replicas
    m = res["metrics"]
    assert {"ops_per_s", "p50_ms", "p95_ms", "setup_s"} <= set(m)
    assert ("stale_p95_ms" in m) == (workload == "repl2.rw50")
    assert 0 < m["p50_ms"]["value"] <= m["p95_ms"]["value"]

