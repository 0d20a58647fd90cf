"""Shared test plumbing.

Two jobs:

1. Register the ``slow`` and ``tier0`` markers.
2. Arm lockdep (``repro.analysis.lockdep``) across the concurrency
   suites: every cluster/server built inside those tests gets ordered
   locks that assert the declared ``LOCK_ORDER`` at acquire time, and
   each test ends by verifying the accumulated cross-thread acquisition
   graph is violation- and cycle-free.
"""
from __future__ import annotations

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test (subprocess meshes)")
    config.addinivalue_line(
        "markers", "tier0: fast pre-commit subset (<60 s total, no heavy "
        "jit) — run with `pytest -m tier0` or scripts/verify.sh --fast")


# ---------------------------------------------------------------------------
# lockdep: runtime lock-order validation across the concurrency suites
# ---------------------------------------------------------------------------

_LOCKDEP_MODULES = {
    "test_concurrent_pipeline",
    "test_dataflow_scheduler",
    "test_faas_server",
    "test_failure_recovery",
}


@pytest.fixture(autouse=True)
def _lockdep_guard(request):
    """Enable the runtime lock-order validator for the concurrency
    suites.  ``enable()`` runs BEFORE the test body so objects the test
    constructs get instrumented locks; teardown fails the test on any
    recorded order violation (even one swallowed by an executor) or on a
    cycle in the cross-thread acquisition graph."""
    mod = getattr(request, "module", None)
    name = getattr(mod, "__name__", "").rpartition(".")[2]
    if name not in _LOCKDEP_MODULES:
        yield
        return
    from repro.analysis import lockdep
    lockdep.enable()
    problems = None
    try:
        yield
        problems = lockdep.verify()
    finally:
        lockdep.disable()
    assert not problems, "lockdep:\n  " + "\n  ".join(problems)
