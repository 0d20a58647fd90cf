"""Keys that come from the request: ``KV.get/set/delete/scan`` taking an
int32 hash the request carries, against a plain reference (one Python
dict per replica, the same Lamport and last-writer-wins rules) on seeded
records at 256 slots of 250 floats.

Contracts under test:

* a request-keyed get/set/delete agrees with the same op by the literal
  key string;
* a request-keyed set of a key the arena does not hold changes no leaf,
  does not advance the clock, and returns ``ok=False`` (a literal set of
  the same key would insert it); a carried hash of 0, the empty slot's
  key, reads and writes nothing;
* a bucket-64 scan fold of Zipf-skewed one-field updates, hot keys
  repeated inside the batch, equals the updates applied one by one;
* the batched read map of distinct keys equals per-request gets;
* literal-key handlers keep their static op log, key hashes, read-only
  flag and traced program; the engine counts keyed ops per dispatch.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import spans
from repro.core import Cluster, enoki_function, get_function
from repro.core.faas import (KV, FunctionSpec, VectorCodec,
                             compile_batched_handler)
from repro.core.keygroup import KeygroupSpec
from repro.core.store import (Store, kv_get, kv_set, store_contents,
                              store_new)
from repro.core.versioning import MAX_NODES, fnv1a, pack_version

jax.config.update("jax_platform_name", "cpu")

SLOTS, WIDTH, FIELDS = 256, 250, 10
FF = WIDTH // FIELDS
NODE = 3


def names(n=SLOTS):
    return [f"user{i}" for i in range(n)]


def filled(seed=0, slots=SLOTS, width=WIDTH, empty=0, writer=1) -> Store:
    """``slots - empty`` records keyed by ``user<i>``'s hash at seeded
    slots, stamped by ``writer`` at clock 1; ``empty`` slots left free."""
    rng = np.random.default_rng(seed)
    keys = np.zeros(slots, np.int32)
    live = rng.permutation(slots)[:slots - empty]
    keys[live] = [fnv1a(k) for k in names(slots - empty)]
    vv = np.zeros(MAX_NODES, np.int32)
    vv[writer] = 1
    occupied = keys != 0
    return Store(
        keys=jnp.asarray(keys),
        values=jnp.asarray(rng.standard_normal((slots, width),
                                               dtype=np.float32)
                           * occupied[:, None]),
        lengths=jnp.asarray(np.where(occupied, width, 0).astype(np.int32)),
        versions=jnp.asarray(np.where(occupied, int(pack_version(1, writer)),
                                      0).astype(np.int32)),
        vv=jnp.asarray(vv))


class DictReplica:
    """One replica as a plain dict {hash: [version, length, row]}, with
    the node's Lamport clock and the version vector."""

    def __init__(self, store: Store, node_id: int, clock: int = 0):
        self.recs = {h: [v, n, np.asarray(row, np.float32)]
                     for h, (v, n, row) in store_contents(store).items()}
        self.vv = np.array(jax.device_get(store.vv), np.int64)
        self.node_id, self.clock = node_id, clock

    def _tick(self) -> int:
        self.clock = max(self.clock, int(self.vv.max())) + 1
        self.vv[self.node_id] = max(self.vv[self.node_id], self.clock)
        return self.clock

    def get(self, h):
        rec = self.recs.get(int(h))
        if rec is None or rec[1] < 0:
            return np.zeros(WIDTH, np.float32), False
        return rec[2].copy(), True

    def update_field(self, h, f, payload):
        """Read-modify-write of one field by request key: the new clock,
        or -1 (nothing changes) where the key is absent."""
        if int(h) not in self.recs:
            return -1
        row, _ = self.get(h)
        row[f * FF:(f + 1) * FF] = payload
        c = self._tick()
        self.recs[int(h)] = [int(pack_version(c, self.node_id)), WIDTH, row]
        return c

    def merge(self, other: "DictReplica") -> None:
        """Last writer wins by packed version, strictly greater taking."""
        for h, rec in other.recs.items():
            mine = self.recs.get(h)
            if mine is None or rec[0] > mine[0]:
                self.recs[h] = [rec[0], rec[1], rec[2].copy()]
        self.vv = np.maximum(self.vv, other.vv)

    def assert_matches(self, store: Store) -> None:
        got = store_contents(store)
        assert set(got) == set(self.recs)
        for h, (v, n, row) in self.recs.items():
            gv, gn, grow = got[h]
            assert (gv, gn) == (v, n), h
            np.testing.assert_array_equal(np.asarray(grow, np.float32), row)
        np.testing.assert_array_equal(np.asarray(store.vv), self.vv)


def run_kv(store, clock, fn, *args):
    """``fn(kv, *args)`` under jit on a fresh handle; returns (store',
    clock', fn's result)."""
    def pure(store, clock, *args):
        kv = KV(store, clock, NODE, VectorCodec(WIDTH))
        out = fn(kv, *args)
        return kv.state + (out,)
    return jax.jit(pure)(store, clock, *args)


def leaves_equal(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in
               zip(jax.device_get(a), jax.device_get(b)))


def field_update(kv, x):
    h, f, payload = x
    record, _ = kv.get(h)
    record = jax.lax.dynamic_update_slice(record, payload, (f * FF,))
    ok = kv.set(h, record)
    return jnp.where(ok, kv.state[1], -1)


def keyed_read(kv, h):
    record, _ = kv.get(h)
    return record


# ---------------------------------------------------------------------------
# request keys against literal keys
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,tombstone", [("user0", False),
                                            ("user123", False),
                                            ("user7", True)])
def test_keyed_ops_agree_with_literal_keys(name, tombstone):
    """get, set, scan and delete by request-carried hashes leave the same
    arena, clock and answers as the same ops by the literal keys, for a
    live key and for a tombstoned one (a keyed set overwrites its
    slot)."""
    store = filled(seed=1)
    if tombstone:
        slot = int(np.flatnonzero(np.asarray(store.keys) == fnv1a(name))[0])
        store = store._replace(lengths=store.lengths.at[slot].set(-1))
    clock = jnp.int32(5)
    h = jnp.int32(fnv1a(name))
    row = jnp.arange(WIDTH, dtype=jnp.float32)

    def literal(kv):
        before = kv.get(name)
        kv.set(name, row)
        after = kv.get(name), kv.scan([name, "user1"])
        kv.delete(name)
        return before, after, kv.get(name)

    def keyed(kv, h):
        before = kv.get(h)
        ok = kv.set(h, row)
        after = kv.get(h), kv.scan(jnp.stack([h, jnp.int32(fnv1a("user1"))]))
        kv.delete(h)
        return before, after, kv.get(h), ok

    s1, c1, out1 = run_kv(store, clock, literal)
    s2, c2, out2 = run_kv(store, clock, keyed, h)
    assert leaves_equal(s1, s2) and int(c1) == int(c2) == 5 + 2
    for a, b in zip(jax.tree.leaves(out1), jax.tree.leaves(out2[:3])):
        np.testing.assert_array_equal(a, b)
    assert bool(out2[3])
    assert bool(out1[0][1]) == (not tombstone)
    np.testing.assert_array_equal(out1[1][0][0], row)


@pytest.mark.parametrize("empty", [0, 16])
def test_keyed_set_of_an_absent_key_changes_nothing(empty):
    """No slot, value, length, version, version-vector entry or clock
    moves, and ``ok`` is False, on a full arena and on one with empty
    slots, where a literal set of the same key inserts it."""
    store = filled(seed=2, empty=empty)
    absent = fnv1a("nobody")
    assert absent not in set(np.asarray(store.keys).tolist())
    clock = jnp.int32(9)
    row = jnp.ones((WIDTH,), jnp.float32)
    s, c, ok = run_kv(store, clock, lambda kv, h: kv.set(h, row),
                      jnp.int32(absent))
    assert not bool(ok) and int(c) == 9
    assert leaves_equal(s, store)
    s_lit, c_lit, none = run_kv(store, clock,
                                lambda kv: kv.set("nobody", row))
    assert none is None
    assert (absent in set(np.asarray(s_lit.keys).tolist())) == (empty > 0)


@pytest.mark.parametrize("op", ["get", "set", "delete", "scan"])
def test_keyed_hash_zero_is_absent(op):
    """0 is the empty slot's key, which no record holds: a request that
    carries it reads nothing and writes nothing, even where the arena
    has empty slots for it to match."""
    store = filled(seed=7, empty=16)
    clock = jnp.int32(9)
    row = jnp.ones((WIDTH,), jnp.float32)
    handler = {"get": lambda kv, h: kv.get(h),
               "set": lambda kv, h: kv.set(h, row),
               "delete": lambda kv, h: kv.delete(h),
               "scan": lambda kv, h: kv.scan(h[None])}[op]
    s, c, out = run_kv(store, clock, handler, jnp.int32(0))
    assert leaves_equal(s, store) and int(c) == 9
    if op == "get":
        np.testing.assert_array_equal(out[0], np.zeros(WIDTH))
        assert not bool(out[1])
    elif op == "set":
        assert not bool(out)
    elif op == "scan":
        np.testing.assert_array_equal(out[0], np.zeros((1, WIDTH)))
        assert not bool(out[1][0])


# ---------------------------------------------------------------------------
# the batched fold and map, against the dict reference
# ---------------------------------------------------------------------------

def zipf_keys(rng, n, slots=SLOTS, theta=0.99):
    p = np.arange(1, slots + 1, dtype=np.float64) ** -theta
    return rng.choice(slots, n, p=p / p.sum())


@pytest.mark.parametrize("n", [64, 41])
def test_scan_fold_of_skewed_updates_equals_one_by_one(n):
    """A bucket-64 scan fold of one-field updates, Zipf-skewed so the hot
    keys repeat inside the batch (and one key the arena lacks), equals
    the dict reference applying them one by one: the arena, the clock and
    each update's returned clock; padded steps answer zeros."""
    rng = np.random.default_rng(n)
    store = filled(seed=3)
    ref = DictReplica(store, NODE, clock=4)
    spec = FunctionSpec(name="kv_field_update", handler=field_update,
                        keygroups=["kvkg"], codec_width=WIDTH)
    example = (np.int32(0), np.int32(0), np.zeros(FF, np.float32))
    bh = compile_batched_handler(spec, NODE, example)
    assert not bh.read_only
    assert (bh.keyed_gets, bh.keyed_sets) == (1, 1)
    assert bh.key_hashes == ()
    hashes = np.array([fnv1a(names()[k]) for k in zipf_keys(rng, 64)],
                      np.int32)
    hashes[5] = fnv1a("nobody")
    assert collections.Counter(hashes[:n].tolist()).most_common(1)[0][1] > 2
    fields = rng.integers(0, FIELDS, 64).astype(np.int32)
    payloads = rng.standard_normal((64, FF), dtype=np.float32)
    valid = np.arange(64) < n
    new, clock, ys, _ = bh(store, jnp.int32(4),
                           (jnp.asarray(hashes), jnp.asarray(fields),
                            jnp.asarray(payloads)), jnp.asarray(valid))
    want = [ref.update_field(hashes[i], fields[i], payloads[i])
            for i in range(n)]
    np.testing.assert_array_equal(np.asarray(ys)[:n], want)
    assert want[5] == -1
    assert not np.asarray(ys)[n:].any()
    assert int(clock) == ref.clock
    ref.assert_matches(new)


def test_read_map_of_distinct_keys_equals_per_request_gets():
    store = filled(seed=4)
    ref = DictReplica(store, NODE)
    spec = FunctionSpec(name="kv_keyed_read", handler=keyed_read,
                        keygroups=["kvkg"], codec_width=WIDTH)
    bh = compile_batched_handler(spec, NODE, np.int32(0))
    assert bh.read_only and (bh.keyed_gets, bh.keyed_sets) == (1, 0)
    rng = np.random.default_rng(5)
    hashes = np.array([fnv1a(names()[k])
                       for k in rng.permutation(SLOTS)[:64]], np.int32)
    out_store, _, ys, _ = bh(store, jnp.int32(0), jnp.asarray(hashes),
                             jnp.ones(64, bool))
    assert out_store is store
    for h, y in zip(hashes, np.asarray(ys)):
        row, found = kv_get(store, jnp.int32(h))[::3]
        assert bool(found)
        np.testing.assert_array_equal(y, row)
        np.testing.assert_array_equal(y, ref.get(h)[0])


# ---------------------------------------------------------------------------
# literal-key handlers unchanged; keyed ops counted
# ---------------------------------------------------------------------------

def _acc(kv, x):
    cur, _ = kv.get("acc")
    kv.set("acc", cur + x)
    return cur + x


def _peek(kv, x):
    vals, _ = kv.scan(["a", "b", "c"])
    return vals[0]


def test_literal_handlers_keep_their_static_facts_and_program():
    """Op log, key hashes and read-only flag as before, no keyed op; and
    the literal handler traces to the program of the store ops it names
    (the trace-time hash and the inserting write)."""
    acc = compile_batched_handler(
        FunctionSpec(name="kv_acc", handler=_acc, keygroups=["k"],
                     codec_width=8), 0, jnp.zeros((8,), jnp.float32))
    assert acc.op_log == [("get", 32), ("set", 32)]
    assert acc.key_hashes == (fnv1a("acc"),) and not acc.read_only
    assert (acc.keyed_gets, acc.keyed_sets) == (0, 0)
    peek = compile_batched_handler(
        FunctionSpec(name="kv_peek", handler=_peek, keygroups=["k"],
                     codec_width=8), 0, jnp.zeros((1,), jnp.float32))
    assert peek.op_log == [("scan", 96)] and peek.read_only
    assert peek.key_hashes == tuple(fnv1a(k) for k in "abc")
    assert (peek.keyed_gets, peek.keyed_sets) == (0, 0)

    codec = VectorCodec(8)
    store, clock, x = store_new(64, 8, MAX_NODES), jnp.int32(0), jnp.ones(8)

    def via_kv(store, clock, x):
        kv = KV(store, clock, 0, codec)
        y = _acc(kv, x)
        return kv.state + (y,)

    def via_store(store, clock, x):
        h = fnv1a("acc")
        row, length, _, _ = kv_get(store, h)
        cur = codec.decode(row, length)
        enc, n = codec.encode(cur + x)
        store, clock, _ = kv_set(store, h, enc, n, clock, 0)
        return store, clock, cur + x

    assert (str(jax.make_jaxpr(via_kv)(store, clock, x))
            == str(jax.make_jaxpr(via_store)(store, clock, x)))


@enoki_function(name="kk_update", keygroups=["kkkg"], codec_width=WIDTH)
def kk_update(kv, x):
    return field_update(kv, x)


@enoki_function(name="kk_read", keygroups=["kkkg"], codec_width=WIDTH)
def kk_read(kv, h):
    return keyed_read(kv, h)


@enoki_function(name="kk_literal", keygroups=["kklitkg"], codec_width=4)
def kk_literal(kv, x):
    kv.set("k", x)
    return x[:1]


def test_engine_counts_keyed_ops_per_dispatch(tmp_path):
    """``EngineStats.keyed_ops`` sums the keyed ops of the valid requests
    dispatched; each ``enoki.dispatch`` span carries the handler's keyed
    gets and sets per request, 0 for a literal-key handler."""
    c = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                measure_compute=False)
    c.create_keygroup(KeygroupSpec(name="kkkg", slots=SLOTS,
                                   value_width=WIDTH), ["edge"])
    c.nodes["edge"].stores["kkkg"] = filled(seed=6)
    example = (np.int32(0), np.int32(0), np.zeros(FF, np.float32))
    c.deploy(get_function("kk_update"), ["edge"], example_input=example)
    c.deploy(get_function("kk_read"), ["edge"], example_input=np.int32(0))
    c.deploy(get_function("kk_literal"), ["edge"],
             example_input=np.zeros(4, np.float32))
    hs = [np.int32(fnv1a(k)) for k in names(5)]
    ups = [(h, np.int32(i), np.full(FF, i, np.float32))
           for i, h in enumerate(hs)]
    for fn, xs in (("kk_update", ups), ("kk_read", hs),
                   ("kk_literal", [np.ones(4, np.float32)] * 2)):
        c.invoke_batch(fn, "edge", xs)          # compile outside the trace
    k0 = c.engine.stats.keyed_ops
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        c.invoke_batch("kk_update", "edge", ups)
        c.invoke_batch("kk_read", "edge", hs[:3])
        c.invoke_batch("kk_literal", "edge", [np.ones(4, np.float32)] * 2)
    assert c.engine.stats.keyed_ops - k0 == 5 * 2 + 3
    got = {r.attrs["fn"]: (r.attrs["n"], r.attrs["keyed_gets"],
                           r.attrs["keyed_sets"])
           for r in spans.records() if r.name == spans.DISPATCH}
    assert got == {"kk_update": (5, 1, 1), "kk_read": (3, 1, 0),
                   "kk_literal": (2, 0, 0)}
