"""The control: the keygroup held one precision below the configuration's
float32 (the program's own bfloat16 arena) must come out not correct."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_cpu  # noqa: E402


def test_bfloat16_records_fail_the_check(tmp_path):
    res = bench_cpu.run("repl2.rw50", seed=17, tmp_path=tmp_path,
                        dtype="bfloat16")
    assert res["correct"] is False
    c = {k: v["value"] for k, v in res["checks"].items()}
    assert c["read_unwritten"] > 0
    assert c["arena_diff.edge"] > 0 and c["arena_diff.edge2"] > 0
    assert c["lost"] == 0


def test_bfloat16_records_fail_the_check_in_the_closed_loop(tmp_path):
    res = bench_cpu.run(bench_cpu.CLOSED, seed=19, tmp_path=tmp_path,
                        rate=2000.0, clients=4,
                        root=bench_cpu.closed_cell_root(tmp_path),
                        dtype="bfloat16")
    assert res["correct"] is False
    c = {k: v["value"] for k, v in res["checks"].items()}
    assert c["read_unwritten"] > 0
    assert c["arena_diff.edge"] > 0 and c["arena_diff.edge2"] > 0
    assert c["lost"] == 0
