"""Device time of the batched fold (the ``jit_scanned`` and ``jit_mapped``
executables) in the traced window, in microseconds per request the engine
folded there."""
FOLD_MODULES = ("jit_scanned", "jit_mapped")


def read(run):
    if run.trace is None or not run.counters["requests_flushed"]:
        return None
    s = run.trace.modules_matching(FOLD_MODULES)
    if s <= 0:
        return None
    return s * 1e6 / run.counters["requests_flushed"]
