"""The readers of the program's spans (``bench/spanread.py`` and the five
metrics on it) on a hand-made record set: exact values, the window and
its tail cut, and nothing where nothing was recorded or the program has
no recorder."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from repro.analysis import spans  # noqa: E402

MS = 1_000_000
T0 = 10_000 * MS
T1 = T0 + 3_000 * MS
RUN = types.SimpleNamespace(t0_ns=T0, t1_ns=T1)
METRICS = ("queue_p95_ms", "serve_p95_ms", "host_us_per_req",
           "scan_pad_share", "repl_lag_p95_ms")


def _rec(name, id_, start_ms, end_ms, parent=0, ticket=-1, **attrs):
    return spans.Record(name, T0 + int(start_ms * MS), T0 + int(end_ms * MS),
                        1, id_, parent, ticket, attrs or None)


def _dispatch(id_, start_ms, kind, n, bucket):
    return _rec("enoki.dispatch", id_, start_ms, start_ms + 1, parent=1,
                fn="f", node="edge", kind=kind, n=n, bucket=bucket)


def _request(id_, start_ms, end_ms, dispatch, lost=0):
    return _rec("enoki.request", id_, start_ms, end_ms, ticket=id_,
                cycle=1, dispatch=dispatch, lost=lost)


RECORDS = [
    _rec("enoki.turn", 10, 0, 100),
    _rec("enoki.cycle", 1, 1, 90, parent=10, windows=2, requests=8),
    _dispatch(2, 10, "scan", 3, 8),
    _dispatch(3, 20, "map", 5, 8),
    _dispatch(4, 2_400, "scan", 17, 64),
    _dispatch(5, -5, "scan", 1, 64),             # before the window
    _rec("enoki.dispatch", 6, 30, 31, parent=1),  # failed before its n
    _rec("enoki.fetch", 11, 40, 70, parent=2),
    _rec("enoki.sleep", 12, 100, 105),
    _rec("enoki.submit", 13, 0, 1),
    _rec("enoki.submit", 14, 5, 6),
    _request(30, 0, 12, 2),                      # queue 10, serve 2
    _request(31, 5, 25, 3),                      # queue 15, serve 5
    _request(32, 2_300, 2_600, 4),               # past the tail cut
    _request(33, 8, 40, 0, lost=1),              # lost
    _request(34, 9, 30, 99),                     # dispatch not recorded
    _rec("enoki.replicate", 20, 50, 51, bytes=64, targets=1, seqs=(5,)),
    _rec("enoki.replicate", 21, 60, 61, bytes=64, targets=1, seqs=(6,)),
    _rec("enoki.replicate", 22, 2_500, 2_501, bytes=64, targets=1,
         seqs=(7,)),
    _rec("enoki.replicate", 23, 65, 66, bytes=64, targets=0, seqs=()),
    _rec("enoki.merge", 24, 69, 70, parent=2, k=2, k_padded=2, seqs=(5, 6)),
    _rec("enoki.merge", 25, 2_550, 2_560, parent=4, k=1, k_padded=1,
         seqs=(7,)),
]

EXPECTED = {
    "queue_p95_ms": 15.0,
    "serve_p95_ms": 5.0,
    # (turn 100 - fetch 30) + two submits of 1, over the four answered
    "host_us_per_req": 72_000.0 / 4,
    # scans in the window: (8 - 3) + (64 - 17) padded of 8 + 64 steps
    "scan_pad_share": 100.0 * 52 / 72,
    # seq 5: 70 - 50, seq 6: 70 - 60; seq 7 is past the cut
    "repl_lag_p95_ms": 20.0,
}


@pytest.fixture
def recorded():
    spans.clear()
    for r in RECORDS:
        spans.RECORDER.add(r)
    yield
    spans.clear()


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_the_exact_value(metric, recorded):
    got = spec.load_reader(metric).read(RUN)
    assert got == pytest.approx(EXPECTED[metric], rel=1e-12)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_on_no_records(metric):
    spans.clear()
    assert spec.load_reader(metric).read(RUN) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_outside_the_window(metric, recorded):
    later = types.SimpleNamespace(t0_ns=T1 + MS, t1_ns=T1 + 10_000 * MS)
    assert spec.load_reader(metric).read(later) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_without_the_recorder(metric, recorded,
                                                   monkeypatch):
    """A checkout whose program has no recorder: the reader says nothing
    and raises nothing."""
    import repro.analysis
    monkeypatch.delattr(repro.analysis, "spans")
    monkeypatch.setitem(sys.modules, "repro.analysis.spans", None)
    assert spec.load_reader(metric).read(RUN) is None
