"""The arithmetic of the check and of the metrics, on hand-made histories:
percentiles, spreads, staleness, and each count the reference compares."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import reference, stats  # noqa: E402


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, float("inf")], 95) == float("inf")
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_staleness_on_a_hand_made_history():
    # key 0 writes: v2 due 100, v3 due 200, v4 due 300; key 1: v5 due 50
    w_due = np.array([100, 200, 300, 50])
    w_key = np.array([0, 0, 0, 1])
    w_ver = np.array([2, 3, 4, 5])
    # reads: (due, key, version returned)
    r = np.array([[150, 0, 2],      # newest due before it: fresh
                  [250, 0, 2],      # missed v3 (due 200): 50
                  [350, 0, 2],      # missed v3 and v4: oldest v3: 150
                  [350, 0, 4],      # fresh
                  [120, 0, 1],      # fill; missed v2 (due 100): 20
                  [90, 0, 1],       # no write due yet: fresh
                  [60, 1, 1],       # missed v5: 10
                  [60, 1, 5]])      # fresh
    got = stats.read_staleness_ns(r[:, 0], r[:, 1], r[:, 2], w_due, w_key,
                                  w_ver)
    np.testing.assert_array_equal(got, [0, 50, 150, 0, 20, 0, 10, 0])
    # by acknowledgement, v3 acked at 320 after v4 at 260: a read of v2
    # due at 300 missed v4 alone (40), one due at 250 missed none
    ack = np.array([110, 320, 260, 55])
    got = stats.read_staleness_ns(np.array([300, 250]), np.array([0, 0]),
                                  np.array([2, 2]), ack, w_key, w_ver)
    np.testing.assert_array_equal(got, [40, 0])


def _case():
    """A 6-slot arena, 2 request keys, writer node 0, and five requests:
    update k0 (row A), read k0, update k1 (row B), update k0 (row C),
    read k0."""
    keys = np.array([11, 22, 33, 44, 55, 66], np.int32)
    values = np.arange(24, dtype=np.float32).reshape(6, 4)
    fill = reference.Fill(keys=keys, values=values,
                          hot_slots=np.array([4, 1]), writer_id=0)
    rows = {0: np.full(4, 0.5, np.float32), 1: np.full(4, 1.5, np.float32),
            2: np.full(4, 2.5, np.float32)}
    obs = reference.Observed(
        kind=np.array([1, 0, 1, 1, 0]), key=np.array([0, 0, 1, 0, 0]),
        update_id=np.array([0, -1, 1, 2, -1]),
        ticket=np.arange(5), send_ns=np.array([0, 10, 20, 30, 40]),
        done_ns=np.array([5, 15, 25, 35, 45]),
        failed=np.zeros(5, bool),
        outputs=[np.int32(2), rows[0], np.int32(3), np.int32(4), rows[2]])
    want = reference.expected_arena(fill, 0, {0: (4, rows[2]),
                                              1: (3, rows[1])}, 4)
    arena = {k: np.array(v) for k, v in want.items()}
    return obs, fill, rows, arena


def test_a_sound_history_passes():
    obs, fill, rows, arena = _case()
    counts, clocks = reference.check(obs, fill, rows.get, 0,
                                     {"edge": arena})
    assert counts == {"lost": 0, "update_bad": 0, "read_unwritten": 0,
                      "read_regress": 0, "arena_diff.edge": 0}
    np.testing.assert_array_equal(clocks, [2, 2, 3, 4, 4])
    assert arena["versions"][4] == 4 * 64 and arena["vv"][0] == 4


def test_each_fault_is_counted():
    obs, fill, rows, arena = _case()
    obs.outputs[1] = rows[1]                    # another key's row
    obs.outputs[3] = np.int32(3)                # a clock taken twice
    obs.done_ns[4] = -1                         # never answered
    arena["values"][0, 0] += 1                  # a record changed
    counts, _ = reference.check(obs, fill, rows.get, 0, {"edge": arena})
    assert counts["read_unwritten"] == 1
    assert counts["lost"] == 1
    assert counts["update_bad"] >= 1
    assert counts["arena_diff.edge"] >= 1


def test_a_read_from_the_future_and_a_regression_are_counted():
    obs, fill, rows, arena = _case()
    obs.outputs[1] = rows[2]            # names the update sent at 30,
    obs.done_ns[1] = 25                 # answered at 25: not yet written
    counts, _ = reference.check(obs, fill, rows.get, 0, {"edge": arena})
    assert counts["read_unwritten"] == 1
    obs, fill, rows, arena = _case()
    obs.outputs[4] = fill.values[4]     # the fill, after a read of v2
    counts, _ = reference.check(obs, fill, rows.get, 0, {"edge": arena})
    assert counts["read_regress"] == 1 and counts["read_unwritten"] == 0


def test_a_lower_precision_read_is_unwritten():
    obs, fill, rows, arena = _case()
    import ml_dtypes
    obs.outputs[1] = rows[0].astype(ml_dtypes.bfloat16)
    counts, _ = reference.check(obs, fill, rows.get, 0, {"edge": arena})
    assert counts["read_unwritten"] == 1


def test_fnv1a_matches_known_values():
    assert reference.fnv1a("") == 0x811C9DC5 & 0x7FFFFFFF
    assert reference.fnv1a("user0") != reference.fnv1a("user1")
