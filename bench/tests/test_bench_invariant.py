"""The first three cells keep, seed for seed, what their runs are made of
since function kinds, key distributions, client loops and references
became files of their own: the digests below were taken from the
benchmark before that change (``bench_cpu.digests``: schedule, fill,
served requests, warmed buckets, at 2,048 records and a 10 s window)."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_cpu  # noqa: E402

BUCKETS = [1, 8, 64, 256]
# (cell, seed): (requests, schedule, fill, first 2,000 requests served,
# functions warmed at every bucket)
DIGESTS = {
    ("repl2.rw50", 1): (40000, "8dcb31d0b284d957", "bbc4dd5a3b199eda",
                        "bc8247b059fa4501", ["kv_read", "kv_update_0"]),
    ("repl2.rw50", 2**33 + 7): (40000, "0b2495317f10d192", "4c284ec0eb17c6f8",
                                "a194a2e1c811047d",
                                ["kv_read", "kv_update_0"]),
    ("single.rw50", 1): (40000, "8dcb31d0b284d957", "bbc4dd5a3b199eda",
                         "bc8247b059fa4501", ["kv_read", "kv_update_0"]),
    ("single.rw50", 2**33 + 7): (40000, "0b2495317f10d192",
                                 "4c284ec0eb17c6f8", "a194a2e1c811047d",
                                 ["kv_read", "kv_update_0"]),
    ("repl2.r100", 1): (64000, "02fbb3efd3a18f39", "bbc4dd5a3b199eda",
                        "bde6b20446b72ebd", ["kv_read"]),
    ("repl2.r100", 2**33 + 7): (64000, "1138b18e0772a9d1", "4c284ec0eb17c6f8",
                                "bde6b20446b72ebd", ["kv_read"]),
}


def check_digests(workload: str, seed: int, root=bench_cpu.ROOT) -> None:
    n, schedule, fill, served, fns = DIGESTS[(workload, seed)]
    got = bench_cpu.digests(workload, seed, root)
    assert (got["n"], got["schedule"], got["fill"], got["requests"]) == (
        n, schedule, fill, served)
    assert got["buckets"] == {fn: BUCKETS for fn in fns}


@pytest.mark.parametrize("workload,seed", sorted(DIGESTS))
def test_cell_keeps_its_schedule_fill_requests_and_warm_up(workload, seed):
    check_digests(workload, seed)


@pytest.mark.parametrize("seed", [1, 2**33 + 7])
def test_request_keys_that_are_all_records_take_the_fills_own_hashes(seed):
    """Where the request keys are all the records, request key i is record
    i under the fill's own hash: every hash distinct and non-zero, no name
    hashed; the named path's other slots hold the same hashes."""
    from bench import deploy
    keys, hot = deploy.fill_layout(seed, bench_cpu.SLOTS,
                                   bench_cpu.SLOTS, named=False)
    np.testing.assert_array_equal(hot, np.arange(bench_cpu.SLOTS))
    assert keys.dtype == np.int32 and np.all(keys > 0)
    assert np.unique(keys).size == keys.size
    named, slots = deploy.fill_layout(seed, bench_cpu.SLOTS, 1)
    np.testing.assert_array_equal(np.delete(named, slots),
                                  np.delete(keys, slots))
