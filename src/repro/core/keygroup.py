"""Keygroups — Enoki/FReD's unit of replication.

Two flavours cover the edge-FaaS scale and the TPU scale:

* ``ArenaKeygroup`` — a string-keyed KV arena (``store.Store``) with a
  replication policy; what the paper's Python functions see via ``kv.*``.
* ``TensorKeygroup`` — an arbitrary pytree of arrays (model parameters, a
  session KV cache, a data-pipeline cursor) with a scalar step-version and a
  pluggable merge rule.  This is how the paper's technique becomes a
  first-class feature of the training/serving framework: the hot path only
  ever touches the *local* replica; ``replication.py`` reconciles replicas
  off the hot path.

Merge rules for tensor keygroups:
  lww     — replica with the higher version wins wholesale (sessions/cursors)
  mean    — elementwise average (parameter averaging / local SGD)
  diloco  — delta-based outer optimizer (optim/diloco.py supplies the step)
  max     — elementwise max (CRDT counters, metrics high-water marks)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ReplicationPolicy
from repro.core import crdt
from repro.core.store import Store, merge_stores, store_new
from repro.kernels.enoki_merge.kernel import check_merge_width


@dataclasses.dataclass(frozen=True)
class KeygroupSpec:
    name: str
    policy: ReplicationPolicy = ReplicationPolicy.REPLICATED
    # arena keygroups
    slots: int = 64
    value_width: int = 64
    dtype: Any = jnp.float32
    # tensor keygroups
    merge: str = "lww"            # lww | mean | max | diloco
    # owner node for PEER_FETCH / CLOUD_CENTRAL placements
    owner: Optional[str] = None

    def __post_init__(self):
        # replicas merge row tiles in VMEM: refuse a width the merge
        # kernel cannot hold now, not at the first merge on a chip
        check_merge_width(self.value_width, self.dtype)


def arena_new(spec: KeygroupSpec, num_nodes: int) -> Store:
    return store_new(spec.slots, spec.value_width, num_nodes, spec.dtype)


@jax.tree_util.register_pytree_node_class
class TensorKeygroup:
    """A replicated pytree with a version and a merge rule."""

    def __init__(self, tree: Any, version: jnp.ndarray, merge: str = "lww"):
        self.tree = tree
        self.version = version
        self.merge = merge

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.tree, self.version), self.merge

    @classmethod
    def tree_unflatten(cls, merge, children):
        tree, version = children
        return cls(tree, version, merge)

    # -- API ----------------------------------------------------------------
    @classmethod
    def create(cls, tree: Any, merge: str = "lww") -> "TensorKeygroup":
        return cls(tree, jnp.zeros((), jnp.int32), merge)

    def write(self, new_tree: Any) -> "TensorKeygroup":
        return TensorKeygroup(new_tree, self.version + 1, self.merge)

    def merged_with(self, other: "TensorKeygroup") -> "TensorKeygroup":
        return merge_tensor_keygroups(self, other)


def merge_tensor_keygroups(a: TensorKeygroup, b: TensorKeygroup) -> TensorKeygroup:
    if a.merge != b.merge:
        raise ValueError(f"merge-rule mismatch: {a.merge} vs {b.merge}")
    if a.merge == "lww":
        take_b = b.version > a.version
        tree = jax.tree.map(lambda x, y: jnp.where(take_b, y, x), a.tree, b.tree)
        version = jnp.maximum(a.version, b.version)
    elif a.merge == "mean":
        tree = jax.tree.map(lambda x, y: (x + y) / 2, a.tree, b.tree)
        version = jnp.maximum(a.version, b.version)
    elif a.merge == "max":
        tree = jax.tree.map(crdt.max_merge, a.tree, b.tree)
        version = jnp.maximum(a.version, b.version)
    else:
        raise ValueError(
            f"merge rule {a.merge!r} needs the replication engine "
            "(diloco merges are stateful; see optim/diloco.py)")
    return TensorKeygroup(tree, version, a.merge)


def merge_arena_keygroups(a: Store, b: Store) -> Store:
    return merge_stores(a, b)
