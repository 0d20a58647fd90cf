"""The node-local key-value store (FReD-replica analogue).

JAX requires static shapes, so a store replica is a fixed-capacity *arena*:

    keys      (S,)    int32   FNV-1a key hashes, 0 == empty slot
    values    (S, V)  dtype   fixed-width payload rows (padded)
    lengths   (S,)    int32   actual payload length; -1 == tombstone
    versions  (S,)    int32   packed lamport versions (see versioning.py)
    vv        (N,)    int32   version vector: highest clock seen per node

All operations are pure functions (jit-friendly); the imperative ``kv.get`` /
``kv.set`` programming model of the paper's Listing 1 is recovered by the
``KV`` handle in ``faas.py`` which threads a ``Store`` through the handler.

A write returns ``ok``: False when it found neither its key nor an empty
slot (arena overflow), or, for a write by a key the request carries
(``insert=False``), when the arena does not hold that key.  A dropped write
changes nothing.  ``KV.set`` hands the ``ok`` of a request-keyed write back to
the handler; a literal-key write's ``ok`` is not surfaced (ROADMAP R2).

Thread-safety: a ``Store`` is an immutable NamedTuple of arrays, so every
function here is safe to call from any thread — "mutation" is producing a
new arena and rebinding a node's reference, which ``Cluster`` serializes
behind per-node locks (see cluster.py); snapshots handed to the replication
queues therefore never change under a concurrent reader.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.versioning import VERSION_DTYPE, pack_version
from repro.kernels.enoki_merge.kernel import enoki_merge_rows


class Store(NamedTuple):
    keys: jnp.ndarray       # (S,) int32
    values: jnp.ndarray     # (S, V)
    lengths: jnp.ndarray    # (S,) int32; -1 marks a tombstone
    versions: jnp.ndarray   # (S,) int32 packed
    vv: jnp.ndarray         # (N,) int32 version vector

    @property
    def slots(self) -> int:
        return self.keys.shape[0]

    @property
    def value_width(self) -> int:
        return self.values.shape[1]


def store_new(slots: int, value_width: int, num_nodes: int,
              dtype=jnp.float32) -> Store:
    return Store(
        keys=jnp.zeros((slots,), jnp.int32),
        values=jnp.zeros((slots, value_width), dtype),
        lengths=jnp.zeros((slots,), jnp.int32),
        versions=jnp.zeros((slots,), VERSION_DTYPE),
        vv=jnp.zeros((num_nodes,), jnp.int32),
    )


def store_select(pred, a: Store, b: Store) -> Store:
    """``pred ? a : b`` over every arena leaf (pred: scalar bool, traced ok).

    The workhorse of conditional writes (kv_set/kv_delete).
    """
    pred = jnp.asarray(pred)

    def sel(x, y):
        p = pred.reshape((1,) * x.ndim) if x.ndim else pred
        return jnp.where(p, x, y)

    return jax.tree.map(sel, a, b)


# ---------------------------------------------------------------------------
# Single-key ops
# ---------------------------------------------------------------------------

def _locate(store: Store, key_hash, carried: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Canonical slot probe.  Returns ``(slot, found, ok)``:

    * ``slot``  — the matching slot when ``found``, else the first empty
      slot (the dynamic-key fallback assignment),
    * ``found`` — whether ``key_hash`` already occupies a slot (live OR
      tombstoned; occupancy, not liveness),
    * ``ok``    — False only on arena overflow (no match and no empty
      slot); callers drop the write.

    ``carried`` (static) marks a hash the request carries.  Such a hash
    may be 0, the empty slot's key, which no record holds (``fnv1a``
    never returns it): it is never ``found``, so it reads as absent and
    writes nothing.  A literal key traces exactly as before.

    Slot-alignment contract: when a keygroup's keys were pre-assigned at
    deploy time (``store_assign_slots`` stamps each key into its
    canonical slot as a version-0 tombstone), the argmax probe lands on
    the same slot on every replica, which is the invariant the
    elementwise merge path (``merge_stores_aligned``) relies on."""
    match = store.keys == key_hash
    found = match.any()
    if carried:
        found = found & (key_hash != 0)
    empty = store.keys == 0
    slot = jnp.where(found, jnp.argmax(match), jnp.argmax(empty))
    ok = found | empty.any()
    return slot, found, ok


def kv_get(store: Store, key_hash, carried: bool = False
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (value_row, length, version, found).

    Tombstone-read contract: ``_locate``'s ``found`` means the key
    occupies a slot, but the ``found`` returned HERE is liveness — a
    tombstoned key (length < 0, written by ``kv_delete`` or by the
    deploy-time slot pre-assignment) reads as absent: zero value, zero
    length, found=False.  Its version still reads through so causal
    consumers can observe the delete.  ``carried``: as in ``_locate``."""
    slot, found, _ = _locate(store, key_hash, carried)
    live = found & (store.lengths[slot] >= 0)
    value = jnp.where(live, store.values[slot], jnp.zeros_like(store.values[slot]))
    length = jnp.where(live, store.lengths[slot], 0)
    version = jnp.where(found, store.versions[slot], 0)
    return value, length, version, live


def kv_set(store: Store, key_hash, value_row, length, clock, node_id,
           insert: bool = True) -> Tuple[Store, jnp.ndarray, jnp.ndarray]:
    """Write.  Returns (store', new_clock, ok).

    The node's lamport clock advances past everything this replica has seen
    (max of vv) so versions from causally-later writes always dominate.

    ``insert`` (static) decides what a key the arena does not hold does:
    True upserts it into the first empty slot; False (a key the request
    carries) writes nothing — no slot, value, clock or version-vector
    entry moves — and returns ``ok=False``.  Such a write only overwrites
    the slot its key already occupies (live or tombstoned), so two
    replicas never claim one empty slot for different keys and slot
    alignment holds; a hash of 0 is absent (``_locate``'s ``carried``).
    """
    slot, found, ok = _locate(store, key_hash, carried=not insert)
    new_clock = jnp.maximum(clock, store.vv.max()) + 1
    version = pack_version(new_clock, node_id)
    write = ok if insert else found  # drop on overflow / on an absent key

    def apply(s: Store) -> Store:
        return Store(
            keys=s.keys.at[slot].set(key_hash),
            values=s.values.at[slot].set(value_row.astype(s.values.dtype)),
            lengths=s.lengths.at[slot].set(length),
            versions=s.versions.at[slot].set(version),
            vv=s.vv.at[node_id].max(new_clock),
        )

    new_store = store_select(write, apply(store), store)
    return new_store, jnp.where(write, new_clock, clock), write


def kv_delete(store: Store, key_hash, clock, node_id, carried: bool = False
              ) -> Tuple[Store, jnp.ndarray, jnp.ndarray]:
    """Tombstone write (length = -1) so deletes replicate like updates.
    ``carried``: as in ``_locate``."""
    zero = jnp.zeros((store.value_width,), store.values.dtype)
    slot, found, _ = _locate(store, key_hash, carried)
    new_clock = jnp.maximum(clock, store.vv.max()) + 1
    version = pack_version(new_clock, node_id)

    def apply(s: Store) -> Store:
        return Store(
            keys=s.keys,
            values=s.values.at[slot].set(zero),
            lengths=s.lengths.at[slot].set(-1),
            versions=s.versions.at[slot].set(version),
            vv=s.vv.at[node_id].max(new_clock),
        )

    new_store = store_select(found, apply(store), store)
    return new_store, jnp.where(found, new_clock, clock), found


def kv_scan(store: Store, key_hashes, carried: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Vectorised multi-get: (values (K,V), lengths (K,), found (K,)).
    ``carried``: as in ``_locate``."""
    def one(h):
        v, l, _, f = kv_get(store, h, carried)
        return v, l, f

    return jax.vmap(one)(jnp.asarray(key_hashes, jnp.int32))


def kv_set_fold(store: Store, key_hashes, rows, lengths, clock, node_id
                ) -> Tuple[Store, jnp.ndarray, jnp.ndarray]:
    """Batched upsert: the sequential fold of N ``kv_set``s as ONE traced op.

    ``jax.lax.scan`` threads (store, clock) through the writes in order, so
    per-key last-writer-wins, version stamping, and the final clock match N
    separate ``kv_set`` calls exactly — while the device sees a single
    dispatch instead of N round-trips.  Returns (store', clock', oks (B,)).
    """
    def step(carry, inp):
        s, c = carry
        h, row, ln = inp
        s2, c2, ok = kv_set(s, h, row, ln, c, node_id)
        return (s2, c2), ok

    xs = (jnp.asarray(key_hashes, jnp.int32), rows,
          jnp.asarray(lengths, jnp.int32))
    (new_store, new_clock), oks = jax.lax.scan(step, (store, clock), xs)
    return new_store, new_clock, oks


# ---------------------------------------------------------------------------
# Replica merge (the anti-entropy inner op)
# ---------------------------------------------------------------------------

def merge_stores(a: Store, b: Store) -> Store:
    """LWW merge of replica ``b`` into ``a`` (pure; commutative up to slot
    permutation, and convergent: merged *contents* are order-independent).

    1. keys present in both  -> keep the higher packed version,
    2. keys only in ``b``    -> insert into a's empty slots (rank-matched),
    3. version vectors       -> elementwise max.

    O(S^2) comparisons; S is small (<=256) for arena keygroups.  Large tensor
    keygroups use slot-aligned merges (see replication.py) or the
    ``enoki_merge`` Pallas kernel instead.
    """
    S = a.slots
    b_live = b.keys != 0
    # --- 1. matched keys -------------------------------------------------
    match = (a.keys[:, None] == b.keys[None, :]) & b_live[None, :]   # (Sa, Sb)
    a_has_match = match.any(axis=1)
    b_idx = jnp.argmax(match, axis=1)                                 # (Sa,)
    b_versions = b.versions[b_idx]
    take_b = a_has_match & (b_versions > a.versions)

    def sel(av, bv):
        mask = take_b.reshape(take_b.shape + (1,) * (av.ndim - 1))
        return jnp.where(mask, bv[b_idx], av)

    keys = jnp.where(take_b, b.keys[b_idx], a.keys)
    values = sel(a.values, b.values)
    lengths = jnp.where(take_b, b.lengths[b_idx], a.lengths)
    versions = jnp.where(take_b, b_versions, a.versions)

    # --- 2. b-only keys -> empty slots of a -------------------------------
    b_matched = match.any(axis=0)                                     # (Sb,)
    b_new = b_live & ~b_matched
    empty = keys == 0
    # rank-match: the i-th new b key goes to the i-th empty a slot
    empty_rank = jnp.cumsum(empty) - 1                                # (Sa,)
    new_rank = jnp.cumsum(b_new) - 1                                  # (Sb,)
    num_empty = empty.sum()
    # for each a slot: which new b key lands here (if any)?
    lands = (empty[:, None] & b_new[None, :]
             & (empty_rank[:, None] == new_rank[None, :]))            # (Sa, Sb)
    has_insert = lands.any(axis=1)
    src = jnp.argmax(lands, axis=1)
    # respect capacity: ranks beyond num_empty simply find no empty slot (mask
    # already guarantees that since empty_rank < num_empty on empty slots).
    del num_empty

    def ins(cur, bv):
        mask = has_insert.reshape(has_insert.shape + (1,) * (cur.ndim - 1))
        return jnp.where(mask, bv[src], cur)

    keys = jnp.where(has_insert, b.keys[src], keys)
    values = ins(values, b.values)
    lengths = jnp.where(has_insert, b.lengths[src], lengths)
    versions = jnp.where(has_insert, b.versions[src], versions)

    # --- 3. version vectors ------------------------------------------------
    vv = jnp.maximum(a.vv, b.vv)
    return Store(keys=keys, values=values, lengths=lengths,
                 versions=versions, vv=vv)


# one fused dispatch per merge instead of ~40 eager op dispatches (the
# delivery profile is dominated by merges under replicated workloads).
# jit's cache is keyed by arena shape, so every keygroup geometry
# compiles once and is shared by all nodes/threads.  This is the
# FALLBACK path — slot-aligned keygroups take merge_stores_aligned /
# merge_snapshots_fused below.
merge_stores_jit = jax.jit(merge_stores)


# ---------------------------------------------------------------------------
# Device-resident merge path: slot-aligned arenas + fused multi-way merge
# ---------------------------------------------------------------------------

@jax.jit
def arena_clone(store: Store) -> Store:
    """Deep-copy an arena into fresh device buffers.

    Snapshot hygiene for donation: anything pushed into a delivery queue
    or shared across nodes must be a clone, never a live reference to an
    arena a later fold/merge may donate."""
    with jax.named_scope("enoki.clone"):
        # XLA inserts the copies itself, with no op metadata; the barrier
        # (free: the compiled program is the same) carries the scope
        return jax.tree.map(jnp.copy, jax.lax.optimization_barrier(store))


def merge_stores_aligned(a: Store, b: Store) -> Store:
    """Elementwise LWW merge for SLOT-ALIGNED replicas.

    Precondition: ``a.keys == b.keys`` slot for slot (deploy-time key
    pre-assignment, see ``store_assign_slots``).  Matching then costs
    nothing — each slot is its own match — and the merge degenerates to
    the per-row versioned select the ``enoki_merge_rows`` Pallas kernel
    implements: O(S·V) instead of ``merge_stores``'s O(S²) probe.  Runs
    the real kernel on TPU and interpret mode elsewhere.

    Bit-compatible with ``merge_stores`` on aligned arenas: strictly
    greater version takes ``b``'s row (ties keep ``a``), version vectors
    max elementwise.  Keys follow the winning row so a dynamic key that
    ``b`` wrote into a still-empty canonical slot inserts correctly; what
    this path canNOT express is two replicas claiming the same empty slot
    for DIFFERENT novel keys — impossible for deployed handlers (their
    key sets are pre-assigned), which is why alignment is tracked per
    keygroup and anything else takes the ``merge_stores`` fallback.
    """
    take_b = b.versions > a.versions
    values, versions = enoki_merge_rows(a.values, a.versions,
                                        b.values, b.versions)
    return Store(
        keys=jnp.where(take_b, b.keys, a.keys),
        values=values,
        lengths=jnp.where(take_b, b.lengths, a.lengths),
        versions=versions,
        vv=jnp.maximum(a.vv, b.vv),
    )


@functools.lru_cache(maxsize=None)
def merge_many_fn(aligned: bool):
    """Jitted K-way merge: fold a tuple of snapshots into an accumulator
    arena with ONE device dispatch (``lax.scan`` over the stacked
    snapshots).  jit's cache keys on the pytree structure, so each
    (aligned, K, geometry) combination traces once.  The accumulator is
    donated: its caller's reference dies with the dispatch."""
    body = merge_stores_aligned if aligned else merge_stores

    def many(acc: Store, snaps) -> Store:
        with jax.named_scope("enoki.merge"):
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *snaps)
            out, _ = jax.lax.scan(lambda s, snap: (body(s, snap), None),
                                  acc, stacked)
        return out

    return jax.jit(many, donate_argnums=(0,))


# K is padded up to a small bucket set so warm delivery never sees a new
# pytree structure (a new K would retrace); beyond the largest bucket the
# exact K runs — still one dispatch, just a fresh trace.
SNAPSHOT_K_BUCKETS = (1, 2, 4, 8, 16, 32)


def padded_k(k: int) -> int:
    """K snapshots after padding to ``SNAPSHOT_K_BUCKETS`` (K itself
    beyond the largest bucket)."""
    return next((b for b in SNAPSHOT_K_BUCKETS if b >= k), k)


def merge_snapshots_fused(acc: Store, snaps: Sequence[Store], *,
                          aligned: bool) -> Store:
    """Merge K queued snapshots into ``acc``, in order, as ONE dispatch.

    Order-preserving: identical to folding ``merge_stores`` (or the
    aligned variant) left to right, which is what the sequential
    delivery loop used to do — so (arrival, seq) LWW semantics are
    bit-identical.  K is padded to the next ``SNAPSHOT_K_BUCKETS`` entry
    by repeating the LAST snapshot: LWW merge is idempotent (matched
    rows need a strictly greater version to win, vv max is idempotent),
    so the repeats are no-ops.
    """
    snaps = tuple(snaps)
    if not snaps:
        return acc
    snaps = snaps + (snaps[-1],) * (padded_k(len(snaps)) - len(snaps))
    return merge_many_fn(bool(aligned))(acc, snaps)


def store_assign_slots(store: Store, assignments: Dict[int, int]
                       ) -> Tuple[Store, bool]:
    """Stamp a deploy-time key→slot layout into an arena (host-side).

    Each key hash is written into its canonical slot as a version-0
    tombstone (length -1, zero payload): reads still see it as absent,
    ``merge_stores`` treats it exactly like any occupied slot, and
    ``_locate``'s argmax probe now lands on the same slot on every
    replica that received the same layout — which is what makes the
    elementwise ``merge_stores_aligned`` path valid.

    Returns ``(store', ok)``.  ``ok`` is False when the layout cannot be
    applied — a slot already holds a DIFFERENT key, or the hash already
    lives in some other slot (dynamic writes beat the assignment): the
    caller must mark the keygroup unaligned and keep the O(S²) fallback.
    """
    keys = np.array(jax.device_get(store.keys))
    lengths = np.array(jax.device_get(store.lengths))
    occupied = {int(k): i for i, k in enumerate(keys) if k != 0}
    changed = False
    for h, slot in assignments.items():
        h = int(h)
        cur = int(keys[slot])
        if cur == h:
            continue
        if cur != 0 or h in occupied:
            return store, False
        keys[slot] = h
        lengths[slot] = -1
        occupied[h] = slot
        changed = True
    if not changed:
        return store, True
    return store._replace(keys=jnp.asarray(keys),
                          lengths=jnp.asarray(lengths)), True


def store_contents(store: Store) -> dict:
    """Host-side canonical view {key_hash: (version, length, value)} for tests."""
    out = {}
    # one transfer for the whole arena instead of four
    keys, values, lengths, versions, _ = jax.device_get(store)
    for i, k in enumerate(keys):
        if k != 0:
            out[int(k)] = (int(versions[i]), int(lengths[i]),
                           values[i].tolist())
    return out


def stores_equal(a: Store, b: Store) -> bool:
    """Exact equality of two arenas as REPLICAS: same occupied keys with
    the same versions, lengths and value rows, same version vector — slot
    layout ignored (merge order may permute slots without changing what
    any read observes).  The determinism checks of the parallel pump
    compare stores with this.  Vectorized: one sort per arena, so
    deployment-sized arenas compare in a few host passes."""
    ha, hb = jax.device_get(a), jax.device_get(b)
    if not np.array_equal(ha.vv, hb.vv):
        return False
    rows = []
    for h in (ha, hb):
        live = np.flatnonzero(h.keys)
        rows.append(live[np.argsort(h.keys[live], kind="stable")])
    ra, rb = rows
    return len(ra) == len(rb) and all(
        np.array_equal(x[ra], y[rb]) for x, y in zip(ha[:4], hb[:4]))
