"""The trace reduction on a short window recorded on a TPU v5e
(``bench/tests/data/repl2_rw50.xplane.pb.gz``: a quarter second of
``repl2.rw50`` offered 6,400 requests a second, traced as a ``--trace 1``
run traces it): the names the readers match are the names the chip's
trace holds, and the numbers they make lie where they can."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tracing  # noqa: E402
from bench.kernels import merge_bytes  # noqa: E402

TRACE = pathlib.Path(__file__).parent / "data" / "repl2_rw50.xplane.pb.gz"
SLOTS, WIDTH = 262144, 256          # kv1k_repl2's arena, float32


def _summary():
    return tracing.summarize(tracing.load(str(TRACE)))


def test_chip_trace_has_one_device_and_the_window():
    t = tracing.load(str(TRACE))
    assert list(t.ops) == ["/device:TPU:0"]
    lo, hi = tracing.window_of(t)
    assert 0.2e9 < hi - lo < 0.4e9


def test_readers_find_their_names_in_a_chip_trace():
    s = _summary()
    assert 0 < s.busy_s < s.window_s
    for module in ("jit_scanned", "jit_many", "jit_arena_clone"):
        assert s.module_s.get(module, 0) > 0, module
    seconds, calls = s.ops_matching("enoki_merge_rows")
    assert calls > 0 and seconds > 0
    share = calls * merge_bytes(SLOTS, WIDTH, 4) / seconds / 819e9
    assert 0.2 < share <= 1.0
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["idle_gaps"]) <= s.window_s - s.busy_s + 1e-9
