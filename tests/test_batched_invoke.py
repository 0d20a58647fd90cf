"""Batched invocation engine: equivalence with sequential invoke (the
tentpole invariant), per-request timing, bucket padding, the read-only vmap
path, and the submit/flush coalescing API."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ReplicationPolicy
from repro.core import Cluster, enoki_function, get_function
from repro.core.faas import (KV, FunctionSpec, VectorCodec,
                             compile_batched_handler, compile_handler)
from repro.core.store import (kv_set, kv_set_fold, store_contents, store_new,
                              store_select)
from repro.core.versioning import MAX_NODES, fnv1a

jax.config.update("jax_platform_name", "cpu")


@enoki_function(name="batched_mix", keygroups=["bmixkg"], codec_width=8)
def batched_mix(kv, x):
    """Mixed get/set/scan — exercises the scan-fold store path."""
    cur, found = kv.get("acc")
    kv.set("acc", cur + x)
    tot, _ = kv.scan(["acc"])
    return jnp.stack([cur[0] + x[0], tot[0, 0]])


@enoki_function(name="batched_peek", keygroups=["bmixkg"], codec_width=8)
def batched_peek(kv, x):
    """Read-only — exercises the vmap path."""
    cur, found = kv.get("acc")
    return cur[:2] + x[:2]


def _cluster(policy, owner=None):
    c = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                measure_compute=False)
    c.deploy(get_function("batched_mix"), ["edge", "edge2"], policy=policy,
             owner=owner)
    return c


def _assert_same_state(c1, c2, kg="bmixkg"):
    for name in c1.nodes:
        s1 = c1.nodes[name].stores.get(kg)
        s2 = c2.nodes[name].stores.get(kg)
        assert (s1 is None) == (s2 is None), name
        if s1 is not None:
            for leaf1, leaf2 in zip(s1, s2):
                np.testing.assert_array_equal(np.asarray(leaf1),
                                              np.asarray(leaf2),
                                              err_msg=f"arena at {name}")
        np.testing.assert_array_equal(np.asarray(c1.nodes[name].clock),
                                      np.asarray(c2.nodes[name].clock),
                                      err_msg=f"clock at {name}")


@pytest.mark.parametrize("policy,owner", [
    (ReplicationPolicy.REPLICATED, None),
    (ReplicationPolicy.PEER_FETCH, "edge"),
    (ReplicationPolicy.CLOUD_CENTRAL, "cloud"),
])
def test_batch_equals_sequential_all_placements(policy, owner):
    """64 mixed get/set invocations: byte-identical final arena, vector
    clock, outputs, and per-request timings vs 64 sequential invokes."""
    xs = [np.arange(8, dtype=np.float32) + i for i in range(64)]
    ts = [i * 0.25 for i in range(64)]
    c_seq, c_bat = _cluster(policy, owner), _cluster(policy, owner)

    seq = [c_seq.invoke("batched_mix", "edge", x, t_send=t)
           for x, t in zip(xs, ts)]
    bat = c_bat.invoke_batch("batched_mix", "edge", xs, t_sends=ts)

    assert len(bat) == 64
    for a, b in zip(seq, bat):
        np.testing.assert_array_equal(np.asarray(a.output),
                                      np.asarray(b.output))
        assert a.response_ms == b.response_ms
        assert a.t_received == b.t_received
        assert a.t_applied == b.t_applied
        assert a.kv_ops == b.kv_ops
        assert a.chain == b.chain
    # replication coalescing must converge peers to the same contents
    c_seq.flush_replication()
    c_bat.flush_replication()
    _assert_same_state(c_seq, c_bat)


def test_per_request_network_timing():
    """Each request in a batch keeps its own send/arrival/response
    timeline."""
    c = _cluster(ReplicationPolicy.REPLICATED)
    ts = [0.0, 7.5, 40.0, 41.25]
    xs = [np.ones(8, np.float32)] * 4
    rs = c.invoke_batch("batched_mix", "edge", xs, t_sends=ts)
    for t, r in zip(ts, rs):
        assert r.t_sent == t
        # same link + same static op trace -> same response latency, but
        # anchored at each request's own send time
        assert r.t_received == pytest.approx(t + rs[0].response_ms)
    assert rs[0].response_ms > 0.0


def test_bucket_padding_is_masked_out():
    """A batch of 5 pads to the 8-bucket; padded slots must not write."""
    xs = [np.full(8, float(i), np.float32) for i in range(5)]
    c_seq = _cluster(ReplicationPolicy.REPLICATED)
    c_bat = _cluster(ReplicationPolicy.REPLICATED)
    seq = [c_seq.invoke("batched_mix", "edge", x, t_send=float(i))
           for i, x in enumerate(xs)]
    bat = c_bat.invoke_batch("batched_mix", "edge", xs,
                             t_sends=[float(i) for i in range(5)])
    assert len(bat) == 5
    for a, b in zip(seq, bat):
        np.testing.assert_array_equal(np.asarray(a.output),
                                      np.asarray(b.output))
    c_seq.flush_replication()
    c_bat.flush_replication()
    _assert_same_state(c_seq, c_bat)


def test_read_only_batch_uses_vmap_and_leaves_state_alone():
    c = _cluster(ReplicationPolicy.REPLICATED)
    c.deploy(get_function("batched_peek"), ["edge"])
    assert c.nodes["edge"].batched_handlers["batched_peek"].read_only
    assert not c.nodes["edge"].batched_handlers["batched_mix"].read_only
    c.invoke("batched_mix", "edge", np.ones(8, np.float32))
    before = store_contents(c.nodes["edge"].stores["bmixkg"])
    clock_before = int(c.nodes["edge"].clock)
    rs = c.invoke_batch("batched_peek", "edge",
                        [np.full(8, float(i), np.float32) for i in range(16)],
                        t_sends=[float(i) for i in range(16)])
    # every request saw the same snapshot
    seq = [c.invoke("batched_peek", "edge", np.full(8, float(i), np.float32),
                    t_send=float(i)) for i in range(16)]
    for a, b in zip(seq, rs):
        np.testing.assert_array_equal(np.asarray(a.output),
                                      np.asarray(b.output))
    assert store_contents(c.nodes["edge"].stores["bmixkg"]) == before
    assert int(c.nodes["edge"].clock) == clock_before


def test_oversize_batch_chunks_at_largest_bucket():
    n = 300   # > largest default bucket (256): folded chunk-by-chunk
    xs = [np.full(8, 1.0, np.float32)] * n
    c_seq = _cluster(ReplicationPolicy.REPLICATED)
    c_bat = _cluster(ReplicationPolicy.REPLICATED)
    for i in range(n):
        c_seq.invoke("batched_mix", "edge", xs[i], t_send=float(i))
    bat = c_bat.invoke_batch("batched_mix", "edge", xs,
                             t_sends=[float(i) for i in range(n)])
    assert len(bat) == n
    c_seq.flush_replication()
    c_bat.flush_replication()
    _assert_same_state(c_seq, c_bat)


def test_submit_flush_coalesces_by_function_and_node():
    c = _cluster(ReplicationPolicy.REPLICATED)
    c.deploy(get_function("batched_peek"), ["edge"])
    tickets = []
    for i in range(6):
        fn = "batched_mix" if i % 2 == 0 else "batched_peek"
        tickets.append((c.engine.submit(fn, "edge",
                                        np.full(8, float(i), np.float32),
                                        t_send=float(i)), fn))
    results = c.engine.flush()
    assert len(results) == 6
    for t, fn in tickets:
        assert results[t].chain == [fn]
        assert results[t].t_sent == float(tickets.index((t, fn)))
    assert c.engine.flush() == {}   # queue drained


@enoki_function(name="batched_async_src", keygroups=[],
                async_calls=["batched_async_sink"], codec_width=4)
def batched_async_src(kv, x):
    return x[:2]


@enoki_function(name="batched_async_sink", keygroups=["asinkkg"],
                codec_width=4)
def batched_async_sink(kv, x):
    cur, _ = kv.get("n")
    kv.set("n", cur + 1.0)
    return x[:1]


def test_async_only_downstream_fires_in_both_paths():
    """Functions with ONLY async_calls must trigger their callees (was
    silently skipped before PR 1) — and async latency must not leak into
    the caller's response."""
    c = Cluster({"edge": "edge", "cloud": "cloud"}, measure_compute=False)
    c.deploy(get_function("batched_async_sink"), ["edge"])
    c.deploy(get_function("batched_async_src"), ["edge"])
    x = np.ones(4, np.float32)
    r = c.invoke("batched_async_src", "edge", x)
    assert r.chain == ["batched_async_src", "batched_async_sink"]
    rb = c.invoke_batch("batched_async_src", "edge", [x] * 3,
                        t_sends=[10.0, 11.0, 12.0])
    for sub in rb:
        assert sub.chain == ["batched_async_src", "batched_async_sink"]
        assert sub.response_ms == pytest.approx(r.response_ms)
    contents = store_contents(c.nodes["edge"].stores["asinkkg"])
    assert list(contents.values())[0][2][0] == 4.0   # sink ran 1 + 3 times


@enoki_function(name="batched_pair", keygroups=["pairkg"], codec_width=4)
def batched_pair(kv, x):
    """Tuple-structured input — batching must preserve pytree structure."""
    a, b = x
    cur, _ = kv.get("s")
    kv.set("s", cur + a[:4])
    return a[:2] + b[:2]


def test_pytree_inputs_keep_structure():
    example = (np.zeros(4, np.float32), np.zeros(2, np.float32))
    c = Cluster({"edge": "edge", "cloud": "cloud"}, measure_compute=False)
    c.deploy(get_function("batched_pair"), ["edge"], example_input=example)
    xs = [(np.full(4, float(i), np.float32),
           np.full(2, 10.0 * i, np.float32)) for i in range(6)]
    c2 = Cluster({"edge": "edge", "cloud": "cloud"}, measure_compute=False)
    c2.deploy(get_function("batched_pair"), ["edge"], example_input=example)
    seq = [c.invoke("batched_pair", "edge", x, t_send=float(i))
           for i, x in enumerate(xs)]
    bat = c2.invoke_batch("batched_pair", "edge", xs,
                          t_sends=[float(i) for i in range(6)])
    for a, b in zip(seq, bat):
        np.testing.assert_array_equal(np.asarray(a.output),
                                      np.asarray(b.output))
    _assert_same_state(c, c2, kg="pairkg")


def test_flush_survives_bad_group():
    """An undeployed function in the queue must fail the flush up front,
    with NO side effects and no lost tickets."""
    c = _cluster(ReplicationPolicy.REPLICATED)
    ok = c.engine.submit("batched_mix", "edge", np.ones(8, np.float32))
    bad = c.engine.submit("not_deployed", "edge", np.ones(8, np.float32))
    before = store_contents(c.nodes["edge"].stores["bmixkg"])
    with pytest.raises(KeyError, match="not_deployed"):
        c.engine.flush()
    # nothing dispatched, queue intact
    assert store_contents(c.nodes["edge"].stores["bmixkg"]) == before
    assert len(c.engine.pending()) == 2
    # drop the bad request (public queue-surgery API) and the good one must
    # still be redeemable
    assert c.engine.discard(bad)
    assert not c.engine.discard(bad)      # already gone
    assert [p["ticket"] for p in c.engine.pending()] == [ok]
    results = c.engine.flush()
    assert ok in results and results[ok].chain == ["batched_mix"]


def test_flush_mid_dispatch_failure_keeps_dispatched_results():
    """If a later group's dispatch raises, results of groups that already
    ran (store effects applied) must surface on the NEXT flush."""
    c = _cluster(ReplicationPolicy.REPLICATED)
    c.deploy(get_function("batched_pair"), ["edge"],
             example_input=(np.zeros(4, np.float32),
                            np.zeros(2, np.float32)))
    ok = c.engine.submit("batched_mix", "edge", np.ones(8, np.float32))
    # a LATER group that passes deployment validation but blows up at
    # trace time: plain array where the handler unpacks a 2-tuple
    bad = c.engine.submit("batched_pair", "edge", np.ones(8, np.float32),
                          t_send=1.0)
    with pytest.raises(Exception):
        c.engine.flush()
    # the failing group was dropped at-most-once style (its effects may have
    # committed); nothing left queued to poke
    assert c.engine.pending() == []
    # the good group dispatched (store mutated); its ticket must redeem now
    results = c.engine.flush()
    assert ok in results and results[ok].chain == ["batched_mix"]


@enoki_function(name="batched_gate", keygroups=[], calls=["batched_async_sink"],
                codec_width=4)
def batched_gate(kv, x):
    """Sync downstream gated by the fig-8 convention (first element < 0
    suppresses the call)."""
    return x[:2]


def test_mixed_fire_sync_downstream_matches_sequential():
    """Partial-fire batches: sub-results must stitch back onto the RIGHT
    requests (index remapping), matching sequential routing exactly."""
    c = Cluster({"edge": "edge", "cloud": "cloud"}, measure_compute=False)
    c.deploy(get_function("batched_async_sink"), ["edge"])
    c.deploy(get_function("batched_gate"), ["edge"])
    xs = [np.full(4, v, np.float32) for v in (1.0, -1.0, 2.0, -3.0, 4.0)]
    ts = [float(i) for i in range(5)]
    bat = c.invoke_batch("batched_gate", "edge", xs, t_sends=ts)
    c2 = Cluster({"edge": "edge", "cloud": "cloud"}, measure_compute=False)
    c2.deploy(get_function("batched_async_sink"), ["edge"])
    c2.deploy(get_function("batched_gate"), ["edge"])
    seq = [c2.invoke("batched_gate", "edge", x, t_send=t)
           for x, t in zip(xs, ts)]
    for a, b in zip(seq, bat):
        assert a.chain == b.chain
        assert a.response_ms == b.response_ms
        np.testing.assert_array_equal(np.asarray(a.output),
                                      np.asarray(b.output))
    # the three positive requests fired, the two negative ones were filtered
    assert [r.chain for r in bat] == [
        ["batched_gate", "batched_async_sink"], ["batched_gate"],
        ["batched_gate", "batched_async_sink"], ["batched_gate"],
        ["batched_gate", "batched_async_sink"]]
    _assert_same_state(c, c2, kg="asinkkg")


def test_all_filtered_sync_downstream_still_returns_results():
    """A batch where NO request fires its sync callee must still finalize
    (regression: the wave loop once dropped such frames' results)."""
    c = Cluster({"edge": "edge", "cloud": "cloud"}, measure_compute=False)
    c.deploy(get_function("batched_async_sink"), ["edge"])
    c.deploy(get_function("batched_gate"), ["edge"])
    xs = [np.full(4, -1.0, np.float32)] * 3        # all filtered
    rs = c.invoke_batch("batched_gate", "edge", xs,
                        t_sends=[0.0, 1.0, 2.0])
    assert len(rs) == 3
    assert all(r.chain == ["batched_gate"] for r in rs)
    tk = c.engine.submit("batched_gate", "edge", xs[0])
    out = c.engine.flush()
    assert out[tk].chain == ["batched_gate"]


def test_downstream_cycle_raises_cleanly():
    @enoki_function(name="cycle_a", keygroups=[], calls=["cycle_b"],
                    codec_width=4)
    def cycle_a(kv, x):
        return x[:2]

    @enoki_function(name="cycle_b", keygroups=[], calls=["cycle_a"],
                    codec_width=4)
    def cycle_b(kv, x):
        return x[:2]

    c = Cluster({"edge": "edge", "cloud": "cloud"}, measure_compute=False)
    c.deploy(get_function("cycle_a"), ["edge"])
    c.deploy(get_function("cycle_b"), ["edge"])
    with pytest.raises(RecursionError, match="cycle"):
        c.invoke_batch("cycle_a", "edge", [np.ones(4, np.float32)])


def test_kv_set_fold_matches_sequential_sets():
    store = store_new(16, 4, MAX_NODES)
    clock = jnp.zeros((), jnp.int32)
    keys = [fnv1a(k) for k in ("a", "b", "a", "c")]
    rows = jnp.stack([jnp.full((4,), float(i + 1)) for i in range(4)])
    lens = [4, 4, 4, 4]

    s_seq, c_seq = store, clock
    for h, row, ln in zip(keys, rows, lens):
        s_seq, c_seq, _ = kv_set(s_seq, h, row, ln, c_seq, node_id=2)

    s_fold, c_fold, oks = kv_set_fold(store, keys, rows, lens, clock,
                                      node_id=2)
    assert bool(oks.all())
    np.testing.assert_array_equal(np.asarray(c_seq), np.asarray(c_fold))
    for a, b in zip(s_seq, s_fold):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # last-writer-wins within the batch: "a" holds the THIRD row
    contents = store_contents(s_fold)
    np.testing.assert_array_equal(
        np.asarray(contents[fnv1a("a")][2], np.float32),
        np.full((4,), 3.0, np.float32))


def _fold_handler(kv, x):
    """Read-modify-write of one key, a blind write of another, a scan of
    both; returns values and the clock, so every output depends on the
    state the step saw."""
    cur, _ = kv.get("acc")
    kv.set("acc", cur + x)
    kv.set("last", x - cur)
    tot, _ = kv.scan(["acc", "last"])
    return jnp.concatenate([tot[:, 0], kv.state[1][None].astype(jnp.float32)])


FOLD_SPEC = FunctionSpec(name="fold_skip", handler=_fold_handler,
                         keygroups=["foldkg"], codec_width=8)
FOLD_BUCKET = 64


def _fold_state(node_id=3):
    """A 2,048-slot arena holding both keys and some others, and a clock
    already past zero."""
    store, clock = store_new(2048, 8, MAX_NODES), jnp.int32(5)
    for i, k in enumerate(("acc", "last", "k0", "k1")):
        store, clock, _ = kv_set(store, fnv1a(k),
                                 jnp.full((8,), i + 0.25, jnp.float32), 8,
                                 clock, node_id)
    return store, clock


def _select_fold(store, clock, xs, valid, node_id=3):
    """Reference fold: every step runs the handler, and a select over the
    whole arena keeps its result only where the step is valid."""
    codec = VectorCodec(FOLD_SPEC.codec_width)

    def step(carry, inp):
        (s, c), (x, v) = carry, inp
        kv = KV(s, c, node_id, codec)
        y = _fold_handler(kv, x)
        ns, nc = kv.state
        return (store_select(v, ns, s), jnp.where(v, nc, c)), y

    (fs, fc), ys = jax.lax.scan(step, (store, clock), (xs, valid))
    return fs, fc, ys


FOLD_MASKS = {"n0": range(0), "n1": range(1), "n17": range(17),
              "n64": range(64), "gaps_0_3_40": (0, 3, 40)}


@pytest.mark.parametrize("mask", list(FOLD_MASKS))
def test_fold_skips_padded_steps_exactly(mask):
    """Whatever the mask, the fold that skips padded steps leaves the
    store, the clock and every valid output bit-identical to the fold that
    ran them and selected them away, and to one ``compile_handler`` call
    per valid request in order: with none valid, to the arena and clock
    it was given."""
    node_id = 3
    idx = list(FOLD_MASKS[mask])
    valid = jnp.asarray(np.isin(np.arange(FOLD_BUCKET), idx))
    xs = jnp.asarray(np.random.default_rng(7).standard_normal(
        (FOLD_BUCKET, 8)).astype(np.float32))
    store, clock = _fold_state(node_id)
    bh = compile_batched_handler(FOLD_SPEC, node_id, xs[0])

    ref_s, ref_c, ref_ys = jax.jit(_select_fold, static_argnums=4)(
        store, clock, xs, valid, node_id)
    got_s, got_c, got_ys, _ = bh(jax.tree.map(jnp.copy, store), clock, xs,
                                 valid)
    for leaf, want in zip(got_s, ref_s):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(ref_c))
    np.testing.assert_array_equal(np.asarray(got_ys)[idx],
                                  np.asarray(ref_ys)[idx])

    step = compile_handler(FOLD_SPEC, node_id, xs[0])
    s, c = store, clock
    for i in idx:
        s, c, y, _ = step(s, c, xs[i])
        np.testing.assert_array_equal(np.asarray(got_ys)[i], np.asarray(y))
    for leaf, want in zip(got_s, s):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(c))


def test_fold_guards_each_step_inside_a_counted_donating_scan():
    """``jit_scanned`` at bucket 64 keeps a scan with a known trip count,
    puts the handler's work under a conditional inside it, and still
    consumes the arena it is given."""
    store, clock = _fold_state()
    xs = jnp.zeros((FOLD_BUCKET, 8), jnp.float32)
    valid = jnp.arange(FOLD_BUCKET) < 17
    bh = compile_batched_handler(FOLD_SPEC, 3, xs[0])

    outer = jax.make_jaxpr(bh.jit_scan)(store, clock, xs, valid).jaxpr
    (scan,) = [e for e in outer.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "scan"]
    assert scan.params["length"] == FOLD_BUCKET
    body = scan.params["jaxpr"].jaxpr
    (cond,) = [e for e in body.eqns if e.primitive.name == "cond"]
    writes = {"scatter", "dynamic_update_slice"}
    # the store is written only inside the guarded branch, never beside it
    assert not writes & {e.primitive.name for e in body.eqns}
    skip, run = cond.params["branches"]
    assert writes & {e.primitive.name for e in run.jaxpr.eqns}
    assert not writes & {e.primitive.name for e in skip.jaxpr.eqns}

    hlo = bh.jit_scan.lower(store, clock, xs, valid).compile().as_text()
    assert hlo.startswith("HloModule jit_scanned")
    assert '"known_trip_count":{"n":"64"}' in hlo
    assert " conditional(" in hlo

    bh(store, clock, xs, valid)
    assert store.values.is_deleted() and store.keys.is_deleted()
