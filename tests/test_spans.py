"""The span recorder (``repro.analysis.spans``): off and allocation-free
with no profiler session, the served path's span tree under one, the
bounded buffer, the scopes the jitted fold, clone and merge carry, and
``CompileCounter`` leaving no listener behind."""
import collections
import itertools
import pathlib
import sys
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import spans
from repro.analysis.jitprof import CompileCounter
from repro.core import Cluster, enoki_function, get_function
from repro.core.faas import compile_batched_handler
from repro.core.keygroup import KeygroupSpec, arena_new
from repro.core.store import arena_clone, merge_many_fn
from repro.core.versioning import MAX_NODES
from repro.launch.faas_server import FaasServer

ROOT = pathlib.Path(__file__).resolve().parents[1]

jax.config.update("jax_platform_name", "cpu")


@enoki_function(name="spt_write", keygroups=["sptkg"], codec_width=4)
def spt_write(kv, x):
    kv.set("k", x)
    return x[:1]


@enoki_function(name="spt_read", keygroups=["sptkg"], codec_width=4)
def spt_read(kv, x):
    cur, _ = kv.get("k")
    return cur[:1]


def _cluster():
    """A 2,048-slot keygroup replicated on both edges; writes served at
    ``edge``, reads at ``edge2``."""
    c = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                measure_compute=False)
    c.create_keygroup(KeygroupSpec(name="sptkg", slots=2048, value_width=4),
                      ["edge", "edge2"])
    c.deploy(get_function("spt_write"), ["edge"])
    c.deploy(get_function("spt_read"), ["edge2"])
    return c


def _serve(c, n=12):
    """n writes, then (once their snapshots have arrived) n reads."""
    x = np.ones(4, np.float32)
    with FaasServer(c, window_ms=2.0, client="edge2") as srv:
        for fn in ("spt_write", "spt_read"):
            futs = [srv.submit(fn, x) for _ in range(n)]
            outs = [f.result(timeout=30.0) for f in futs]
            time.sleep(0.05)
    return outs


def test_off_builds_nothing_and_records_nothing(monkeypatch):
    """With no profiler session a site costs the one check: no block is
    allocated in a loop of them, and a served run (fold, clone, merge)
    never builds a span, a request span or a record."""
    assert not spans.on()
    spans.clear()
    start, name = spans.start, spans.DISPATCH
    start(name, 7)                      # warm the call path
    tracemalloc.start()
    try:
        loop = itertools.repeat(None, 10_000)
        base, _ = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        for _ in loop:
            start(name, 7)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak == base                 # not one block, even for a moment
    assert start(name, 7) is None

    def built(*args, **kwargs):
        raise AssertionError("built with no profiler session")

    c = _cluster()
    for attr in ("Span", "Request", "serve"):
        monkeypatch.setattr(spans, attr, built)
    monkeypatch.setattr(spans.RECORDER, "add", built)
    _serve(c)
    c.flush_replication()
    monkeypatch.undo()
    assert spans.records() == [] and spans.dropped() == 0
    assert not spans.RECORDER._served


def test_traced_run_yields_the_span_tree(tmp_path):
    c = _cluster()
    _serve(c, n=1)                      # compile outside the trace
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        _serve(c)
    recs = spans.records()
    by_id = {r.id: r for r in recs}
    names = collections.Counter(r.name for r in recs)
    assert names[spans.SUBMIT] == names[spans.REQUEST] == 24
    for name in (spans.TURN, spans.SLEEP, spans.CYCLE, spans.DISPATCH,
                 spans.STAGE, spans.CALL, spans.FETCH, spans.REPLICATE,
                 spans.MERGE):
        assert names[name] >= 1, name

    kinds = set()
    for r in recs:
        assert r.start <= r.end
        if r.name == spans.REQUEST:
            assert r.attrs["lost"] == 0
            d = by_id[r.attrs["dispatch"]]
            assert d.name == spans.DISPATCH
            assert d.parent == r.attrs["cycle"]
            assert by_id[d.parent].name == spans.CYCLE
            assert r.start <= d.start <= d.end <= r.end
            assert 0 <= r.ticket
        elif r.name == spans.DISPATCH:
            a = r.attrs
            assert 1 <= a["n"] <= a["bucket"] and a["bucket"] in (1, 8, 64)
            kinds.add((a["fn"], a["node"], a["kind"]))
        elif r.name in (spans.STAGE, spans.CALL, spans.FETCH):
            assert by_id[r.parent].name == spans.DISPATCH
        elif r.name == spans.CYCLE:
            assert r.attrs["requests"] >= r.attrs["windows"] >= 1
        elif r.name == spans.SUBMIT:
            assert r.ticket >= 0
    assert kinds == {("spt_write", "edge", "scan"),
                     ("spt_read", "edge2", "map")}

    reps = [r for r in recs if r.name == spans.REPLICATE]
    merges = [r for r in recs if r.name == spans.MERGE]
    assert all(r.attrs["targets"] == 1 and r.attrs["bytes"] > 0
               for r in reps)
    merged = {s for m in merges for s in m.attrs["seqs"]}
    assert merged and merged <= {s for r in reps for s in r.attrs["seqs"]}
    for m in merges:
        assert m.attrs["k"] == len(m.attrs["seqs"]) <= m.attrs["k_padded"]
        assert by_id[m.parent].name == spans.DISPATCH

    # the same names sit on the host plane of the profile
    sys.path.insert(0, str(ROOT))
    from bench import trace as tracing
    t = tracing.load(tracing.find_xplane(str(tmp_path)))
    host = {e.name for evs in t.host.values() for e in evs}
    assert {spans.SUBMIT, spans.TURN, spans.SLEEP, spans.CYCLE,
            spans.DISPATCH, spans.FETCH, spans.MERGE,
            spans.REPLICATE} <= host
    assert spans.REQUEST not in host


def test_buffer_keeps_the_newest_and_counts_drops():
    rec = spans.Recorder(capacity=3)
    for i in range(5):
        rec.add(spans.Record("x", i, i, 0, i, 0, -1, {}))
    assert [r.id for r in rec.records()] == [2, 3, 4]
    assert rec.dropped() == 2
    rec.clear()
    assert rec.records() == [] and rec.dropped() == 0


def test_ending_a_span_closes_those_an_exception_left_open():
    spans.clear()
    outer = spans.Span(spans.CYCLE, 0)
    inner = spans.Span(spans.DISPATCH, 0)
    assert inner.parent == outer.id
    spans.Span(spans.CALL, 0)           # never ended
    outer.end()
    after = spans.Span(spans.TURN, 0)
    assert after.parent == 0
    after.end()
    assert [r.name for r in spans.records()] == [spans.CYCLE, spans.TURN]
    spans.clear()


def _scope_and_module(lowered):
    return lowered.as_text(debug_info=True), lowered.as_text().split()[1]


@pytest.mark.parametrize("which", ["scanned", "mapped", "many",
                                   "arena_clone"])
def test_jitted_paths_carry_their_scope_and_keep_their_name(which):
    store = arena_new(KeygroupSpec(name="sptkg", slots=64, value_width=4),
                      MAX_NODES)
    if which in ("scanned", "mapped"):
        fn = "spt_write" if which == "scanned" else "spt_read"
        bh = compile_batched_handler(get_function(fn), 0, jnp.zeros(4))
        xs = jnp.zeros((8, 4))
        lowered = (bh.jit_scan.lower(store, jnp.zeros((), jnp.int32), xs,
                                     jnp.ones(8, bool))
                   if which == "scanned" else
                   bh.jit_map.lower(store, jnp.zeros((), jnp.int32), xs))
        scope = "enoki.fold"
    elif which == "many":
        lowered = merge_many_fn(True).lower(store, (store, store))
        scope = "enoki.merge"
    else:
        lowered = arena_clone.lower(store)
        scope = "enoki.clone"
    text, module = _scope_and_module(lowered)
    assert f"jit({which})/{scope}/" in text
    assert module == f"@jit_{which}"


def test_compile_counter_unregisters_its_listener():
    from jax._src import monitoring
    with CompileCounter() as cc:
        jax.jit(lambda v: v * 3)(jnp.arange(5)).block_until_ready()
        assert cc._cb in monitoring.get_event_listeners()
    assert cc.events >= 1
    assert cc._cb not in monitoring.get_event_listeners()
