"""The FaaS layer (tinyFaaS role): function registry, deployment, invocation.

The paper's programming model (Listing 1)::

    import kv
    def call(i: str) -> str:
        curr = kv.get(key="current")
        ...
        kv.set(key="current", val=curr)
        return curr

is preserved as::

    @enoki_function(keygroups=["avg"])
    def call(kv, i):
        curr = kv.get("current")
        ...
        kv.set("current", curr)
        return curr

``kv`` is a handle whose get/set/scan/delete trace to pure ops on a
``Store`` threaded through the handler; deployment jit-compiles the wrapper
``(store, clock, input) -> (store', clock', output)``.  As in the paper,
"global imports stay warm": compilation happens once at deploy time, so warm
invocations pay no setup cost.

Values are encoded by per-keygroup codecs (the arena stores fixed-width
rows).  A key is either a *string*, hashed at trace time — static, exactly
like the paper's literal key names, and pre-assigned a slot at deploy — or
an int32 *hash the request carries* (per-user or per-session state), traced
like any other input.  A request-keyed write only overwrites a record its
key already holds and tells the handler whether it did (``KV.set``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.store import Store, kv_delete, kv_get, kv_scan, kv_set
from repro.core.versioning import fnv1a


# ---------------------------------------------------------------------------
# Codecs: python value <-> fixed-width arena row
# ---------------------------------------------------------------------------

class VectorCodec:
    """Float32 vectors up to ``width`` elements (scalars are width-1 views)."""

    def __init__(self, width: int):
        self.width = width

    def encode(self, val) -> Tuple[jnp.ndarray, jnp.ndarray]:
        arr = jnp.atleast_1d(jnp.asarray(val, jnp.float32))
        n = arr.shape[0]
        if n > self.width:
            raise ValueError(f"value of length {n} exceeds arena width {self.width}")
        row = jnp.zeros((self.width,), jnp.float32).at[:n].set(arr)
        return row, jnp.int32(n)

    def decode(self, row: jnp.ndarray, length: jnp.ndarray) -> jnp.ndarray:
        # static-width view; mask the padding so stale bytes never leak
        idx = jnp.arange(self.width)
        return jnp.where(idx < length, row, 0.0)


class BytesCodec:
    """uint8 payloads (for the size-sweep throughput benchmarks)."""

    def __init__(self, width: int):
        self.width = width

    def encode(self, val) -> Tuple[jnp.ndarray, jnp.ndarray]:
        arr = jnp.asarray(val, jnp.uint8)
        n = arr.shape[0]
        row = jnp.zeros((self.width,), jnp.uint8).at[:n].set(arr)
        return row, jnp.int32(n)

    def decode(self, row, length):
        return row  # callers slice by length host-side


# ---------------------------------------------------------------------------
# The kv handle (Listing 1's `import kv`)
# ---------------------------------------------------------------------------

class KV:
    """Functional KV handle: mutating methods rebind the wrapped store.

    Also counts operations and payload bytes — the invocation layer charges
    network costs per op for remote placements (CLOUD_CENTRAL/PEER_FETCH),
    which is how the paper's per-op round-trips (§4.1: 4 ops -> +200 ms)
    are accounted.

    A key is a ``str`` (hashed here, at trace time) or an int32 scalar
    array: a hash the request carries.  Every op enters ``ops``; only
    literal keys enter ``key_hashes``, and request-keyed ops are counted
    in ``keyed_gets`` / ``keyed_sets`` (writes: set and delete) instead —
    all static facts of the trace.
    """

    def __init__(self, store: Store, clock: jnp.ndarray, node_id: int,
                 codec: VectorCodec):
        self._store = store
        self._clock = clock
        self._node_id = node_id
        self._codec = codec
        self.ops: List[Tuple[str, int]] = []   # (kind, payload_bytes)
        # every literal key hash the handler touches — static (hashed at
        # trace time), so one trace enumerates the full literal key set;
        # deploy uses it for canonical slot pre-assignment
        self.key_hashes: List[int] = []
        self.keyed_gets = 0
        self.keyed_sets = 0

    def _hash(self, key, write: bool):
        """(hash, literal?): a string's static hash, logged; or the
        request's traced hash, counted as a keyed op."""
        if isinstance(key, str):
            h = fnv1a(key)
            self.key_hashes.append(h)
            return h, True
        if write:
            self.keyed_sets += 1
        else:
            self.keyed_gets += 1
        return jnp.asarray(key, jnp.int32), False

    # -- paper API ----------------------------------------------------------
    def get(self, key):
        h, literal = self._hash(key, write=False)
        row, length, _, found = kv_get(self._store, h, carried=not literal)
        val = self._codec.decode(row, length)
        nbytes = int(np.dtype(np.float32).itemsize) * self._codec.width
        self.ops.append(("get", nbytes))
        return val, found

    def set(self, key, val):
        """Write ``val`` under ``key``.  A literal key upserts and returns
        nothing; a request key overwrites the record it already holds and
        returns ``ok`` (False: the arena holds no such key, nothing
        changed)."""
        h, literal = self._hash(key, write=True)
        row, length = self._codec.encode(val)
        self._store, self._clock, ok = kv_set(
            self._store, h, row, length, self._clock, self._node_id,
            insert=literal)
        self.ops.append(("set", int(row.nbytes)))
        return None if literal else ok

    def scan(self, keys):
        """Read several keys: a sequence of strings, or an int32 array of
        hashes the request carries."""
        carried = hasattr(keys, "dtype")
        if carried:
            hashes = jnp.asarray(keys, jnp.int32)
            self.keyed_gets += int(hashes.size)
        else:
            hashes = [fnv1a(k) for k in keys]
            self.key_hashes.extend(hashes)
        vals, lengths, founds = kv_scan(self._store, hashes, carried)
        idx = jnp.arange(vals.shape[1])[None, :]
        vals = jnp.where(idx < lengths[:, None], vals, 0.0)
        self.ops.append(("scan", int(vals.nbytes)))
        return vals, founds

    def delete(self, key) -> None:
        h, literal = self._hash(key, write=True)
        self._store, self._clock, _ = kv_delete(
            self._store, h, self._clock, self._node_id, carried=not literal)
        self.ops.append(("delete", 0))

    # -- plumbing -------------------------------------------------------------
    @property
    def state(self) -> Tuple[Store, jnp.ndarray]:
        return self._store, self._clock


# ---------------------------------------------------------------------------
# Function registry + deployment
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FunctionSpec:
    name: str
    handler: Callable            # handler(kv, x) -> y
    keygroups: List[str]
    codec_width: int = 64
    calls: List[str] = dataclasses.field(default_factory=list)  # downstream fns
    async_calls: List[str] = dataclasses.field(default_factory=list)


_REGISTRY: Dict[str, FunctionSpec] = {}


def enoki_function(name: Optional[str] = None, keygroups: Sequence[str] = (),
                   codec_width: int = 64, calls: Sequence[str] = (),
                   async_calls: Sequence[str] = ()):
    """Decorator registering a stateful FaaS function."""

    def wrap(fn: Callable) -> Callable:
        spec = FunctionSpec(name=name or fn.__name__, handler=fn,
                            keygroups=list(keygroups), codec_width=codec_width,
                            calls=list(calls), async_calls=list(async_calls))
        _REGISTRY[spec.name] = spec
        fn.spec = spec
        return fn

    return wrap


def get_function(name: str) -> FunctionSpec:
    return _REGISTRY[name]


def registry() -> Dict[str, FunctionSpec]:
    return dict(_REGISTRY)


def compile_handler(spec: FunctionSpec, node_id: int,
                    example_input: Any) -> Callable:
    """Jit the pure wrapper around the user handler (deploy-time).

    Returns ``step(store, clock, x) -> (store', clock', y, op_log)`` where
    op_log is the static per-invocation (kind, bytes) trace used for network
    accounting (it is identical across invocations by construction: key
    strings and shapes are static, as in the paper's functions).
    """
    codec = VectorCodec(spec.codec_width)
    op_log: List[Tuple[str, int]] = []
    hash_log: List[int] = []

    def pure(store: Store, clock: jnp.ndarray, x):
        kv = KV(store, clock, node_id, codec)
        y = spec.handler(kv, x)
        op_log.clear()
        op_log.extend(kv.ops)
        hash_log.clear()
        hash_log.extend(kv.key_hashes)
        new_store, new_clock = kv.state
        return new_store, new_clock, y

    jitted = jax.jit(pure)
    # trace once to populate the op log and warm the cache (warm start)
    _ = jax.eval_shape(pure, *_example_state(spec, example_input, node_id))

    def step(store, clock, x):
        return jitted(store, clock, x) + (list(op_log),)

    step.op_log = op_log
    step.key_hashes = tuple(dict.fromkeys(hash_log))
    step.read_only = handler_read_only(op_log)
    return step


def handler_read_only(op_log: Sequence[Tuple[str, int]]) -> bool:
    """Whether a deploy-time op trace contains no mutating store ops.

    The router uses this to decide which handlers are safe to re-invoke
    (hedged retries): a mutating handler re-runs its writes and replication
    events on every retry, so only read-only handlers may be hedged.  An
    EMPTY trace (stateless handler) is trivially read-only."""
    return all(k in ("get", "scan") for k, _ in op_log)


def compile_batched_handler(spec: FunctionSpec, node_id: int,
                            example_input: Any) -> Callable:
    """Jit the *batched* pure wrapper (deploy-time) — the §4.2 hot path.

    Returns ``bstep(store, clock, xs, valid, independent=False)`` where
    ``xs`` stacks B request inputs along axis 0 and ``valid`` (B,) bool masks
    bucket padding.  Produces ``(store', clock', ys, op_log)`` with ``ys``
    stacked per-request outputs.  Static facts of the deploy-time trace
    ride on the step: ``op_log``, ``key_hashes`` (literal keys only),
    ``read_only`` and ``keyed_gets`` / ``keyed_sets`` (ops by a key the
    request carries, per invocation).

    Execution strategy, chosen from the handler's static op trace:

    * mutating handlers — a ``jax.lax.scan`` over the batch threads
      (store, clock) through the requests in order, each step's work
      under a ``lax.cond`` on its valid flag (a padded step runs nothing
      and outputs zeros), so per-key last-writer-wins semantics and the
      final clock are EXACTLY those of B sequential invocations — but the
      host pays one dispatch instead of B Python round-trips;
    * read-only handlers (only get/scan ops) — a ``jax.vmap`` over requests
      against the shared store: every request sees the same snapshot and
      runs data-parallel on the device;
    * ``independent=True`` (stateless functions, no keygroup) — vmap with
      per-request throwaway state, matching B fresh-arena invocations.

    Both variants are traced lazily per (batch-bucket, store-shape) and
    cached by jit, so warm batches pay zero setup — the batched analogue of
    the paper's "global imports stay warm".
    """
    codec = VectorCodec(spec.codec_width)
    op_log: List[Tuple[str, int]] = []
    hash_log: List[int] = []
    keyed: Dict[str, int] = {}

    def pure(store: Store, clock: jnp.ndarray, x):
        kv = KV(store, clock, node_id, codec)
        y = spec.handler(kv, x)
        op_log.clear()
        op_log.extend(kv.ops)
        hash_log.clear()
        hash_log.extend(kv.key_hashes)
        keyed.update(gets=kv.keyed_gets, sets=kv.keyed_sets)
        new_store, new_clock = kv.state
        return new_store, new_clock, y

    # trace once at deploy time: populates the static op + key-hash logs
    _ = jax.eval_shape(pure, *_example_state(spec, example_input, node_id))
    read_only = handler_read_only(op_log)

    def scanned(store, clock, xs, valid):
        def run(s, c, x):
            ns, nc, y = pure(s, c, x)
            return (ns, nc), y

        def skip(s, c, x):
            y = jax.eval_shape(pure, s, c, x)[2]
            return (s, c), jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), y)

        def step(carry, inp):
            # a padded step skips the handler on the device (its probe,
            # write and clock), so a bucket of 64 holding 17 requests
            # does the work of 17; its output row reads zeros
            x, v = inp
            return jax.lax.cond(v, run, skip, *carry, x)

        with jax.named_scope("enoki.fold"):
            (fs, fc), ys = jax.lax.scan(step, (store, clock), (xs, valid))
        return fs, fc, ys

    def mapped(store, clock, xs):
        # outputs only: the store result is dropped per-request, so vmap
        # never materialises a batched arena
        with jax.named_scope("enoki.fold"):
            return jax.vmap(lambda x: pure(store, clock, x)[2])(xs)

    # donate the arena through the fold: XLA reuses the input buffers for
    # the output store, so warm folds stop allocating a fresh arena per
    # dispatch.  The caller's reference (nd.stores[kg]) dies with the
    # dispatch — every snapshot that outlives it must be a clone (see
    # cluster._schedule_replication and docs/batched_engine.md
    # "Device-resident store").  jit_map is NOT donated: it hands the
    # caller's own store refs back.
    jit_scan = jax.jit(scanned, donate_argnums=(0,))
    jit_map = jax.jit(mapped)

    def bstep(store, clock, xs, valid, independent: bool = False):
        if independent or read_only:
            # hand back the caller's own store/clock refs: routing them
            # through jit outputs would copy the whole arena per dispatch
            out = (store, clock, jit_map(store, clock, xs))
        else:
            out = jit_scan(store, clock, xs, valid)
        return out + (list(op_log),)

    bstep.op_log = op_log
    bstep.key_hashes = tuple(dict.fromkeys(hash_log))
    bstep.read_only = read_only
    # request-keyed ops per invocation (the dispatch span and
    # ``EngineStats.keyed_ops`` count them)
    bstep.keyed_gets = keyed["gets"]
    bstep.keyed_sets = keyed["sets"]
    bstep.example = example_input
    bstep.jit_scan = jit_scan
    bstep.jit_map = jit_map
    return bstep


def _example_state(spec: FunctionSpec, example_input, node_id):
    from repro.core.store import store_new
    from repro.core.versioning import MAX_NODES

    store = store_new(64, spec.codec_width, MAX_NODES)
    return store, jnp.zeros((), jnp.int32), example_input
