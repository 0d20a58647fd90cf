"""Requests the engine folded per device dispatch over the window
(``EngineStats.requests_flushed / dispatches``)."""


def read(run):
    d = run.counters["dispatches"]
    return run.counters["requests_flushed"] / d if d else None
