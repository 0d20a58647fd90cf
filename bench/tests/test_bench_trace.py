"""The trace reduction: busy and idle share, device time by executable and
by operation, idle gaps charged to the host's activity; on a hand-made
trace with exact arithmetic."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tracing  # noqa: E402
from bench.trace import Event  # noqa: E402


def _hand_made():
    dev = "/device:TPU:0"
    ops = [Event("fusion.1", 100, 50), Event("fusion.2", 140, 30),
           Event("enoki_merge_rows", 300, 100), Event("copy.3", 900, 200)]
    mods = [Event("jit_scanned(7)", 90, 90), Event("jit_many(2)", 290, 120),
            Event("jit_arena_clone(3)", 890, 220)]
    host = {"main": [Event(tracing.WINDOW_ANNOTATION, 0, 1000),
                     Event("submit", 500, 100)],
            "faas-server": [Event("PjitFunction(scanned)", 180, 100),
                            Event("device_get", 420, 470)]}
    return tracing.Trace(ops={dev: ops}, modules={dev: mods}, host=host)


def test_busy_idle_and_device_time_by_name():
    s = tracing.summarize(_hand_made())
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [100,170] + [300,400] + [900,1000] (clipped at the close)
    assert s.busy_s == pytest.approx(270e-9)
    assert s.module_s["jit_scanned"] == pytest.approx(80e-9)
    assert s.module_s["jit_many"] == pytest.approx(100e-9)
    assert s.modules_matching(("jit_scanned", "jit_mapped")) == \
        pytest.approx(80e-9)
    assert s.ops_matching("enoki_merge_rows") == (pytest.approx(100e-9), 1)
    # gaps [0,100] [170,300] [400,900], each charged whole to the host
    # event that covers most of it
    assert s.idle_gaps["faas-server: device_get"] == pytest.approx(500e-9)
    assert s.idle_gaps["faas-server: PjitFunction(scanned)"] == \
        pytest.approx(130e-9)
    assert s.idle_gaps["no host event"] == pytest.approx(100e-9)
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_arena_clone/copy.3",
                                  pytest.approx(100e-9)]
    assert len(b["idle_gaps"]) == 3


def test_a_trace_without_the_window_or_devices_is_refused():
    t = _hand_made()
    t.host["main"] = t.host["main"][1:]
    with pytest.raises(ValueError):
        tracing.summarize(t)
    with pytest.raises(ValueError):
        tracing.summarize(tracing.Trace(ops={}, modules={}, host={}),
                          window=(0, 1))
