"""The YCSB-A cell (``ycsb_a.repl2``): its run through the harness at
2,048 records on the CPU held to ``bench/ycsb_reference.py``, the faults
that reference must see, the scrambled Zipfian key distribution, and the
``keyed_roofline`` reader."""
import json
import pathlib
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_cpu  # noqa: E402

from bench import spec, traffic  # noqa: E402
from repro.analysis import spans  # noqa: E402

ROOT = bench_cpu.ROOT
CELL = "ycsb_a.repl2"
MIX = json.loads((ROOT / "bench/traffic/ycsb_a_open.json").read_text())
CONFIG = json.loads((ROOT / "bench/configs/ycsb_a_repl2.json").read_text())
CHECKS = {"lost", "update_bad", "read_unwritten", "read_regress",
          "arena_diff.edge", "arena_diff.edge2"}


def test_cell_runs_correct_on_the_aligned_merge(tmp_path, capfd):
    """Request-keyed reads at edge2 and one-field updates at edge, through
    ``Cluster``, the engine and ``FaasServer``, agree with the reference
    exactly; every merge takes the slot-aligned kernel."""
    res = bench_cpu.run(CELL, seed=2**33 + 11, tmp_path=tmp_path,
                        rate=200.0)
    bench_cpu.assert_sound(res)
    assert set(res["checks"]) == CHECKS and res["attempted"] == 200
    assert set(res["metrics"]) == {"ops_per_s", "p50_ms", "p95_ms",
                                   "stale_p95_ms", "setup_s"}
    err = capfd.readouterr().err
    assert "fallback=0" in err and "aligned=0 " not in err


def test_configuration_agrees_with_its_kind_and_reference():
    """The record's shape that the configuration states is the one the
    update kind and the reference split into fields."""
    kind = spec.load_part("kinds", "field_update")
    ref = spec.load_module(ROOT / CONFIG["reference"], "the reference")
    assert kind.FIELDCOUNT == ref.FIELDCOUNT == CONFIG["fieldcount"]
    assert CONFIG["record_floats"] == (CONFIG["fieldcount"]
                                       * CONFIG["fieldfloats"])
    assert CONFIG["fieldfloats"] * 4 == CONFIG["fieldlength"]
    assert CONFIG["recordcount"] == CONFIG["requestkeys"] == 262_144
    assert all(kind.field_of(u) == ref.field_of(u) for u in range(100))


def _doctor(monkeypatch, mutate):
    """Runs whose reference is handed what ``mutate`` makes of the
    observed requests and the drained arenas."""
    real = spec.load_module

    def load(path, what):
        mod = real(path, what)
        if what == "the reference":
            inner = mod.check

            def check(obs, fill, rows, writer_id, arenas):
                mutate(obs, fill, rows, arenas)
                return inner(obs, fill, rows, writer_id, arenas)
            mod.check = check
        return mod
    monkeypatch.setattr(spec, "load_module", load)


def _later_field(obs, fill, rows, arenas):
    """One read gets a field of an update of its key sent after the
    read's answer arrived."""
    upd = np.flatnonzero(obs.kind == 1)
    for i in np.flatnonzero(obs.kind == 0):
        later = upd[(obs.key[upd] == obs.key[i])
                    & (obs.send_ns[upd] > obs.done_ns[i])]
        if later.size:
            u = int(obs.update_id[later[0]])
            f, ff = u % 10, fill.values.shape[1] // 10
            out = np.array(obs.outputs[i])
            out[f * ff:(f + 1) * ff] = rows(u)[:ff]
            obs.outputs[i] = out
            return
    raise AssertionError("no read has a later update of its key")


def _dropped_field(obs, fill, rows, arenas):
    """The last update's field write is missing from edge2's arena."""
    u = int(obs.update_id[np.flatnonzero(obs.kind == 1)[-1]])
    i = int(np.flatnonzero(obs.update_id == u)[0])
    slot = fill.hot_slots[int(obs.key[i])]
    f, ff = u % 10, fill.values.shape[1] // 10
    vals = np.array(arenas["edge2"]["values"])
    vals[slot, f * ff:(f + 1) * ff] = fill.values[slot, f * ff:(f + 1) * ff]
    arenas["edge2"]["values"] = vals


@pytest.mark.parametrize("fault,count", [
    (_later_field, "read_unwritten"),
    (_dropped_field, "arena_diff.edge2"),
])
def test_reference_sees_the_fault(fault, count, monkeypatch, tmp_path):
    _doctor(monkeypatch, fault)
    res = bench_cpu.run(CELL, seed=43, tmp_path=tmp_path, rate=300.0)
    assert res["correct"] is False
    c = {k: v["value"] for k, v in res["checks"].items()}
    assert c[count] > 0 and c["lost"] == 0


def test_bfloat16_records_fail_the_check(tmp_path):
    res = bench_cpu.run(CELL, seed=17, tmp_path=tmp_path, dtype="bfloat16")
    assert res["correct"] is False
    c = {k: v["value"] for k, v in res["checks"].items()}
    assert c["read_unwritten"] > 0 and c["lost"] == 0
    assert c["arena_diff.edge"] > 0 and c["arena_diff.edge2"] > 0


# ---------------------------------------------------------------------------
# the key distribution
# ---------------------------------------------------------------------------

def _fnv1a64_java(v: int) -> int:
    """YCSB's ``Utils.fnvhash64`` restated on Python integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) & (2**64 - 1)
    return 2**64 - h if h >= 2**63 else h


def test_scrambled_zipfian_blocks():
    """Blocks do not depend on the seed, hold the mix exactly, scramble
    ranks drawn over YCSB's 10**10 items by its FNV-1a-64, and give the
    hottest key about 1/ZETAN = 1/zeta(10**10, 0.99) of the requests."""
    dist = traffic.distribution(MIX)
    n = 262_144
    a = traffic.make_schedule(MIX, n, 8, 1, 1.0, 1000)
    b = traffic.make_schedule(MIX, n, 8, 2**33 + 9, 1.0, 1000)
    block = dist.block(MIX, n, 0)
    for s in (a, b):
        np.testing.assert_array_equal(
            np.sort(s.kind.astype(np.int64) * n + s.key),
            np.sort(block[:, 0] * n + block[:, 1]))
    assert not np.array_equal(a.key, b.key)
    ops = np.concatenate([dist.block(MIX, n, i) for i in range(120)])
    assert np.all(np.bincount(ops[:, 0]) == [60_000, 60_000])
    assert ops[:, 1].min() >= 0 and ops[:, 1].max() < n
    top = np.bincount(ops[:, 1]).max() / len(ops)
    assert top == pytest.approx(1 / 26.46902820178302, rel=0.05)
    vals = [0, 1, 2, 255, 256, 2**40 + 7, 9_999_999_999]
    np.testing.assert_array_equal(dist.fnvhash64(np.array(vals)),
                                  np.array([_fnv1a64_java(v) for v in vals],
                                           np.uint64))
    assert np.argmax(np.bincount(ops[:, 1])) == _fnv1a64_java(0) % n


# ---------------------------------------------------------------------------
# the keyed_roofline reader
# ---------------------------------------------------------------------------

MS = 1_000_000
T0, T1 = 10_000 * MS, 13_000 * MS


def _dispatch(id_, start_ms, n, **keyed):
    return spans.Record("enoki.dispatch", T0 + start_ms * MS,
                        T0 + (start_ms + 1) * MS, 1, id_, 0, -1,
                        dict(fn="f", node="edge", kind="scan", n=n,
                             bucket=64, **keyed))


def _run(trace_s=2.0):
    summary = types.SimpleNamespace(
        modules_matching=lambda prefixes: trace_s)
    return types.SimpleNamespace(
        t0_ns=T0, t1_ns=T1, trace=summary,
        arena={"slots": 2048, "width": 250, "itemsize": 4},
        peaks={"hbm_bytes_per_s": 819e9})


@pytest.fixture
def keyed_records():
    spans.clear()
    for r in (_dispatch(1, 10, 17, keyed_gets=1, keyed_sets=1),
              _dispatch(2, 20, 40, keyed_gets=1, keyed_sets=0),
              _dispatch(3, 30, 5, keyed_gets=0, keyed_sets=0),
              _dispatch(4, -5, 64, keyed_gets=1, keyed_sets=1)):
        spans.RECORDER.add(r)
    yield
    spans.clear()


def test_keyed_roofline_gives_the_exact_value(keyed_records):
    """(17 x 2 + 40) keyed ops in the window, 1,012 bytes each, over 2 s
    of fold time, over 819 GB/s."""
    got = spec.load_reader("keyed_roofline").read(_run())
    assert got == pytest.approx(100.0 * 74 * 1012 / 2.0 / 819e9, rel=1e-12)


def test_keyed_roofline_reads_nothing_without_keyed_counts():
    """A program whose dispatch spans carry no keyed counts (the parent
    of the change that added them), or a run without a trace."""
    spans.clear()
    spans.RECORDER.add(spans.Record(
        "enoki.dispatch", T0 + MS, T0 + 2 * MS, 1, 1, 0, -1,
        dict(fn="f", node="edge", kind="scan", n=3, bucket=8)))
    reader = spec.load_reader("keyed_roofline")
    assert reader.read(_run()) is None
    spans.clear()
    assert reader.read(_run()) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None
