"""Sharding rules: parameter/optimizer/cache pytrees → PartitionSpecs.

Rules are leaf-name based (megatron-style tensor parallelism over the
``model`` axis, FSDP/ZeRO over ``data``), with divisibility guards: an
assignment that does not divide evenly falls back to replication instead of
failing at lowering (e.g. whisper's vocab 51865 % 16 ≠ 0 → replicated
embedding).  Stacked leading layer dims are never sharded (they are scanned).

  column-parallel (output dim over model):  wq wk wv w_gate w_up w_z w_x
                                            w_q w_k w_v lm_head ...
  row-parallel (input dim over model):      wo w_down w_out w_ff_down
  expert-parallel (experts over model):     moe w_gate/w_up/w_down when
                                            E % model_shards == 0, else the
                                            experts fall back to column/row TP
  vocab-parallel:                           embed (dim 0)

The ``pod`` axis is NEVER assigned to parameters here: parameter replicas
per pod are Enoki keygroups, reconciled by replication.py off the hot path.
(CLOUD_CENTRAL/sync-DP instead folds ``pod`` into the gradient reduction —
see launch/train.py.)
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ParallelConfig, ShapeConfig, StepKind

COL_PARALLEL = {"wq", "wk", "wv", "w_gate", "w_up", "w_ff_gate", "w_ff_up",
                "w_z", "w_x", "w_q", "w_k", "w_v", "lm_head", "patch_proj",
                "frame_proj"}
ROW_PARALLEL = {"wo", "w_down", "w_out", "w_ff_down"}
VOCAB_PARALLEL = {"embed"}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
    return ""


def _in_moe(path) -> bool:
    return any(getattr(e, "key", None) == "moe" for e in path)


def _spec_for(path, leaf, arch: ArchConfig, mesh: Mesh,
              parallel: ParallelConfig) -> P:
    name = _leaf_name(path)
    shape = leaf.shape
    nd = len(shape)
    model = _axis_size(mesh, "model")
    data = _axis_size(mesh, "data")
    assign: list = [None] * nd

    def try_assign(dim: int, axis: str, size: int) -> bool:
        if size > 1 and shape[dim] % size == 0 and assign[dim] is None:
            assign[dim] = axis
            return True
        return False

    if nd >= 2:
        moe_expert_weight = (_in_moe(path)
                             and name in ("w_gate", "w_up", "w_down")
                             and nd >= 3)
        if moe_expert_weight and shape[-3] % model == 0:
            try_assign(nd - 3, "model", model)            # expert-parallel
        elif name in COL_PARALLEL:
            try_assign(nd - 1, "model", model)
        elif name in ROW_PARALLEL:
            try_assign(nd - 2, "model", model)
        elif name in VOCAB_PARALLEL:
            try_assign(0, "model", model)
        # FSDP: shard the largest remaining dim over data
        if parallel.fsdp:
            free = [d for d in range(nd) if assign[d] is None]
            for d in sorted(free, key=lambda d: -shape[d]):
                if try_assign(d, "data", data):
                    break
    return P(*assign)


def param_partition_specs(params: Any, arch: ArchConfig, mesh: Mesh,
                          parallel: ParallelConfig) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(path, leaf, arch, mesh, parallel), params)


def opt_state_specs(params: Any, arch: ArchConfig, mesh: Mesh,
                    parallel: ParallelConfig) -> Any:
    """Specs for one params-shaped moment tree.  ZeRO-1: moments additionally
    sharded over ``data`` even when parameters are not (fsdp=False)."""
    if parallel.fsdp or not parallel.zero1:
        return param_partition_specs(params, arch, mesh, parallel)
    import dataclasses
    zp = dataclasses.replace(parallel, fsdp=True)   # data-shard the moments
    return param_partition_specs(params, arch, mesh, zp)


# ---------------------------------------------------------------------------
# Activations / batches / caches
# ---------------------------------------------------------------------------

def batch_specs(arch: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                parallel: ParallelConfig) -> Any:
    """PartitionSpecs for the input batch dict (matches input_specs keys)."""
    data = _axis_size(mesh, "data")
    bdim = "data" if shape.global_batch % max(data, 1) == 0 and data > 1 else None
    seq = None
    if parallel.seq_shard and shape.step is StepKind.PREFILL:
        seq = "model"
    if shape.step in (StepKind.TRAIN, StepKind.PREFILL):
        specs = {"tokens": P(bdim, seq)}
        if shape.step is StepKind.TRAIN:
            specs["labels"] = P(bdim, seq)
            specs["loss_mask"] = P(bdim, seq)
        if arch.frontend_stub == "clip_patches":
            specs["patch_embeds"] = P(bdim, None, None)
        if arch.frontend_stub == "audio_frames":
            specs["frame_embeds"] = P(bdim, None, None)
        return specs
    return {"token": P(bdim, None)}


def cache_partition_specs(cache: Any, arch: ArchConfig, mesh: Mesh,
                          batch: int, prefer_seq: bool = False) -> Any:
    """KV/state cache specs: batch over ``data``; one trailing dim over
    ``model``.  ``prefer_seq=True`` shards the SEQUENCE dim (the one right
    after batch) — required by the flash-decode partial-softmax path, which
    owns the cross-shard softmax combine (§Perf hillclimb B).  Cache trees
    are stacked (L, B, ...) or nested-stacked (G, n, B, ...); the batch dim
    is located by size match."""
    model = _axis_size(mesh, "model")
    data = _axis_size(mesh, "data")

    def spec(path, leaf) -> P:
        name = _leaf_name(path)
        shape = leaf.shape
        nd = len(shape)
        if nd == 0 or name == "length":
            return P()
        assign = [None] * nd
        # find the batch dim: first dim equal to `batch` after the stack dims
        bdim = None
        for d, s in enumerate(shape):
            if s == batch:
                bdim = d
                break
        if bdim is not None and data > 1 and batch % data == 0:
            assign[bdim] = "data"
        if model > 1 and nd >= 2:
            placed = False
            if prefer_seq and bdim is not None and bdim + 1 < nd \
                    and shape[bdim + 1] % model == 0 \
                    and shape[bdim + 1] >= model:
                assign[bdim + 1] = "model"      # the sequence dim
                placed = True
            if not placed:
                for d in sorted(range(nd - 1, max(nd - 3, -1), -1),
                                key=lambda d: -shape[d]):
                    if d != bdim and assign[d] is None \
                            and shape[d] % model == 0 and shape[d] >= model:
                        assign[d] = "model"
                        break
        return P(*assign)

    return jax.tree_util.tree_map_with_path(spec, cache)


def named(mesh: Mesh, tree_of_specs: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda x: isinstance(x, P))
