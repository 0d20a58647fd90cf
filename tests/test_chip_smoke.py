"""chip_smoke.py's phases at a tiny arena on the CPU (interpreted merge):
the kernel against its reference, then deploy, fill, serve open-loop
through FaasServer, drain, and compare both replicas with the sequential
reference.  The TPU check is not called —
the script itself refuses to run off a TPU."""
import importlib.util
import pathlib
import sys

import numpy as np

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_agree_with_reference():
    cs = _load()
    cluster = cs.deploy(slots=2048)
    ref = cs.fill(cluster, seed=3)
    assert cluster._aligned[cs.KG] is True
    srv, bad = cs.serve(cluster, ref, seed=3, n_requests=48)
    cluster.flush_replication()
    assert bad == []
    assert (srv.stats.served, srv.stats.lost, srv.stats.cycle_errors) == (
        48, 0, 0)
    assert not cluster.engine.errors
    st = cluster.stats
    assert st.merge_aligned > 0 and st.merge_fallback == 0
    assert cs.compare(cluster, ref) == []
    # the accumulator really moved: 12 read-modify-writes, one clock each
    assert ref.clock == 1 + 48 // cs.RMW_EVERY
    acc = cluster.store_of(cs.KG, "edge2")
    slot = ref.slot[cs.fnv1a(cs.ACC)]
    assert not np.array_equal(np.asarray(acc.values[slot]),
                              ref.values[slot])


def test_smoke_kernel_check_agrees():
    """The kernel phase at one whole-arena tile of no multiple of 8 rows
    and at an arena padded across two row tiles."""
    cs = _load()
    assert cs.check_kernel([(1001, 8), (3000, 16)], seed=3) == []
