"""Jit-compilation accounting for the shape-pinning guarantees.

The device-resident store path promises that a warm serving loop never
retraces: every (bucket × keygroup-geometry) shape is executed once at
deploy time (``engine.prewarm``) and the staging buffers / padding masks
are persistent.  ``CompileCounter`` is the measurement side of that
promise — it counts XLA compile requests via ``jax.monitoring`` events
while active, so a test can wrap warm flush cycles and assert the count
stays ZERO (tests/test_perf_paths.py).

Counting events (not cache sizes) catches every compile path: a fresh
``jax.jit`` trace, a new shape on a cached jit, and nested pallas_call
lowering all emit compile-request events; warm cache-hit dispatches emit
none.
"""
from __future__ import annotations

import jax

# every XLA compile request fires monitoring events whose names carry
# this substring (jax 0.4.x: '/jax/compilation_cache/compile_requests_*');
# warm dispatches fire none
COMPILE_EVENT_SUBSTR = "compile_requests"


class CompileCounter:
    """Context manager counting XLA compile requests while active.

    ``events`` is monotone within the block; ``events == 0`` on exit means
    every dispatch inside hit jit's cache.  Listener registration is
    process-global in jax, so instances must not be nested concurrently
    across threads (tests use one at a time).
    """

    def __init__(self):
        self.events = 0
        self._cb = None

    def _on_event(self, name, *args, **kwargs):
        if COMPILE_EVENT_SUBSTR in name:
            self.events += 1

    def __enter__(self) -> "CompileCounter":
        self._cb = self._on_event
        jax.monitoring.register_event_listener(self._cb)
        return self

    def __exit__(self, *exc) -> bool:
        # jax's public monitoring API registers listeners but has no
        # call to remove one
        from jax._src import monitoring
        monitoring.unregister_event_listener(self._cb)
        return False
