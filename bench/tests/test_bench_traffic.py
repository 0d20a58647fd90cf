"""The traffic generator: exact mixes, and schedules that
repeat by seed and differ between seeds only in order and timing."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import traffic  # noqa: E402

RW50 = json.loads((ROOT / "bench/traffic/rw50_open.json").read_text())
MIX_C = json.loads((ROOT / "bench/traffic/r100_open.json").read_text())
MIX_A = RW50


def test_apportion_by_largest_remainder():
    p = np.array([0.5, 0.3, 0.2])
    c = traffic.apportion(7, p)
    assert c.sum() == 7 and np.all(np.abs(c - 7 * p) < 1)
    np.testing.assert_array_equal(traffic.apportion(10, np.full(4, 0.25)),
                                  [3, 3, 2, 2])


def test_block_holds_the_exact_mix():
    ops = traffic.block_ops(MIX_A, 8)
    assert ops.shape == (1000, 2)
    assert np.sum(ops[:, 0] == traffic.READ) == 500
    reads = np.bincount(ops[ops[:, 0] == traffic.READ, 1], minlength=8)
    np.testing.assert_array_equal(reads, [63, 63, 63, 63, 62, 62, 62, 62])
    assert np.all(traffic.block_ops(MIX_C, 8)[:, 0] == traffic.READ)


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_open_schedule_repeats_by_seed(seed):
    a = traffic.make_schedule(MIX_A, 8, 256, seed, 3.0, 400)
    b = traffic.make_schedule(MIX_A, 8, 256, seed, 3.0, 400)
    assert len(a) == 1200
    for x, y in ((a.kind, b.kind), (a.key, b.key), (a.due_ns, b.due_ns),
                 (a.update_id, b.update_id)):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a.row(5), b.row(5))
    np.testing.assert_array_equal(a.row(3000), b.row(3000))
    assert np.all(np.diff(a.due_ns) >= 0)
    assert 0 <= a.due_ns[0] and a.due_ns[-1] < 3e9


def test_seeds_change_order_not_work():
    a = traffic.make_schedule(MIX_A, 8, 256, 1, 3.0, 400)
    b = traffic.make_schedule(MIX_A, 8, 256, 2, 3.0, 400)
    assert len(a) == len(b)
    assert not np.array_equal(a.kind, b.kind)
    assert not np.array_equal(a.due_ns, b.due_ns)
    for s in (a, b):        # whole blocks: the same multiset
        pairs = s.kind[:1000].astype(int) * 8 + s.key[:1000]
        np.testing.assert_array_equal(
            np.bincount(pairs, minlength=16),
            np.bincount(traffic.block_ops(MIX_A, 8) @ [8, 1], minlength=16))
    assert not np.array_equal(a.row(0), b.row(0))


def test_update_rows_are_distinct_and_numbered():
    s = traffic.make_schedule(MIX_A, 8, 256, 4, 3.0, 400)
    upd = s.update_id[s.kind == traffic.UPDATE]
    np.testing.assert_array_equal(upd, np.arange(upd.size))
    rows = {s.row(u).tobytes() for u in range(upd.size)}
    assert len(rows) == upd.size
    assert s.row(0).dtype == np.float32 and s.row(0).shape == (256,)


def test_the_cells_mix_and_other_loops_are_refused():
    """A closed loop is a loop file like the open one; a loop or a key
    distribution that no file names is refused."""
    ops = traffic.block_ops(RW50, 1)
    assert np.sum(ops[:, 0] == traffic.READ) == 500 and np.all(ops[:, 1] == 0)
    closed = traffic.make_schedule(dict(RW50, loop="closed", clients=4), 1,
                                   256, 3, 2.0, 10)
    opened = traffic.make_schedule(RW50, 1, 256, 3, 2.0, 10)
    np.testing.assert_array_equal(closed.kind, opened.kind)
    assert closed.key.dtype == np.int32 and not closed.due_ns.any()
    with pytest.raises(ValueError):
        traffic.make_schedule(dict(RW50, loop="no_such_loop"), 1, 256, 3,
                              2.0, 10)
    with pytest.raises(ValueError):
        traffic.make_schedule(dict(RW50, requestdistribution="no_such"), 1,
                              256, 3, 2.0, 10)
    with pytest.raises(ValueError):
        traffic.make_schedule(dict(RW50, arrivals="bursty"), 1, 256, 3, 2.0,
                              10)
