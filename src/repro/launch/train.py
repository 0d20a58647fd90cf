"""Training step factory — the paper's three data placements as distribution
schemes (DESIGN.md §2):

REPLICATED (Enoki / DiLoCo)
    Parameters+optimizer are *pod-stacked* keygroups: every leaf carries a
    leading ``n_pods`` dim sharded P("pod", ...).  ``train_step`` is a vmap
    over that dim — each pod trains on pod-local data against its local
    replica, so the hot path contains ZERO pod-axis collectives (verified
    structurally by the dry-run).  ``replicate_step`` is a separate jitted
    program: delta exchange over the pod axis (optionally int8-compressed)
    + DiLoCo outer Nesterov.  Staleness bound = replication_period steps.

CLOUD_CENTRAL (the paper's baseline)
    One shared parameter set, batch sharded over ("pod","data") — fully
    synchronous cross-pod DP.  Gradient all-reduce crosses the DCN every
    step: pod collectives ON the hot path.

PEER_FETCH (SyncMesh analogue)
    Parameters sharded over the pod axis (owner pods hold shards); every
    step all-gathers them across the DCN on demand.  Hot-path pod
    collectives again, read-heavy this time.

Single-pod meshes have no ``pod`` axis: all policies coincide with plain
DP×TP and ``replicate_step`` is the identity.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ArchConfig, AttnImpl, EnokiConfig,
                                ParallelConfig, ReplicationPolicy,
                                ShapeConfig, StepKind, TrainConfig)
from repro.models import model_zoo as zoo
from repro.optim import (adafactor_init, adafactor_update, adamw_init,
                         adamw_update, diloco_init, diloco_outer_update,
                         warmup_cosine)
from repro.optim.compression import int8_compress
from repro.parallel.sharding import (batch_specs, named, opt_state_specs,
                                     param_partition_specs)


# ---------------------------------------------------------------------------
# Per-cell defaults
# ---------------------------------------------------------------------------

def default_parallel(arch: ArchConfig, shape: ShapeConfig) -> ParallelConfig:
    n = arch.param_count()
    big = n > 20e9
    return ParallelConfig(
        fsdp=big and shape.step is StepKind.TRAIN,
        zero1=True,
        seq_shard=False,
        remat=("full" if big else "block") if shape.step is StepKind.TRAIN
        else "none",
        use_scan=True,
        optimizer="adafactor" if n > 200e9 else "adamw",
    )


def param_dtype_for(arch: ArchConfig) -> Any:
    # ≥200B params: bf16 weights + adafactor, or HBM can never fit (§Dry-run)
    return jnp.bfloat16 if arch.param_count() > 200e9 else jnp.float32


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

def init_state(arch: ArchConfig, key, parallel: ParallelConfig,
               dtype=None) -> Dict[str, Any]:
    dtype = dtype or param_dtype_for(arch)
    params = zoo.init_params(arch, key, dtype=dtype)
    if parallel.optimizer == "adafactor":
        opt = adafactor_init(params)
    else:
        # fp32 params are their own master copy
        opt = adamw_init(params, keep_master=(dtype == jnp.bfloat16))
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}


def state_shapes(arch: ArchConfig, parallel: ParallelConfig,
                 dtype=None) -> Dict[str, Any]:
    """ShapeDtypeStructs of the train state (dry-run: no allocation)."""
    return jax.eval_shape(
        lambda: init_state(arch, jax.random.PRNGKey(0), parallel, dtype))


def state_specs(state_shape: Dict[str, Any], arch: ArchConfig, mesh: Mesh,
                parallel: ParallelConfig,
                peer_fetch_pod: bool = False) -> Dict[str, Any]:
    pspecs = param_partition_specs(state_shape["params"], arch, mesh, parallel)
    ospecs = jax.tree.map(
        lambda leaf: None, state_shape["opt"])
    # moments/master mirror param leaves by name; reuse the same rule fn
    ospecs = opt_specs_tree(state_shape["opt"], arch, mesh, parallel)
    specs = {"params": pspecs, "opt": ospecs, "step": P()}
    if peer_fetch_pod:
        specs = jax.tree.map(_add_pod_axis_spec, specs,
                             _shapes_of(state_shape),
                             is_leaf=lambda x: isinstance(x, P))
    return specs


def _shapes_of(tree):
    return jax.tree.map(lambda l: tuple(l.shape), tree)


def _add_pod_axis_spec(spec: P, shape: tuple) -> P:
    """PEER_FETCH: additionally shard the largest free divisible dim over
    'pod' (FSDP across the DCN)."""
    assign = list(spec) + [None] * (len(shape) - len(spec))
    free = [d for d in range(len(shape)) if assign[d] is None]
    for d in sorted(free, key=lambda d: -shape[d]):
        if shape[d] % 2 == 0 and shape[d] >= 2:
            assign[d] = "pod"
            break
    return P(*assign)


def opt_specs_tree(opt_shape: Any, arch: ArchConfig, mesh: Mesh,
                   parallel: ParallelConfig) -> Any:
    """Optimizer-state specs: params-shaped subtrees (m/v/master or
    adafactor full) get the ZeRO/param rule; factored row/col vectors and
    counters replicate."""
    from repro.parallel.sharding import _spec_for  # leaf-name based

    import dataclasses as dc
    zp = dc.replace(parallel, fsdp=parallel.fsdp or parallel.zero1)

    def spec(path, leaf):
        names = [getattr(e, "key", None) for e in path]
        if "count" in names or names[-1] in ("row", "col"):
            return P()          # tiny
        return _spec_for(path, leaf, arch, mesh, zp)

    return jax.tree_util.tree_map_with_path(spec, opt_shape)


# ---------------------------------------------------------------------------
# The core single-replica train step
# ---------------------------------------------------------------------------

def make_loss_fn(arch: ArchConfig, parallel: ParallelConfig,
                 impl: AttnImpl = AttnImpl.REFERENCE, mesh=None):
    def loss_fn(params, batch):
        return zoo.lm_loss(arch, params, batch, impl=impl,
                           remat=parallel.remat, mesh=mesh,
                           moe_impl=parallel.moe_impl)
    return loss_fn


def make_step_fn(arch: ArchConfig, parallel: ParallelConfig,
                 cfg: TrainConfig, impl: AttnImpl = AttnImpl.REFERENCE,
                 mesh=None) -> Callable:
    loss_fn = make_loss_fn(arch, parallel, impl, mesh)

    def step(state, batch):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True)(state["params"])
        lr = warmup_cosine(state["step"], cfg.lr, cfg.warmup_steps,
                           cfg.total_steps)
        if parallel.optimizer == "adafactor":
            new_params, new_opt, om = adafactor_update(
                grads, state["opt"], state["params"], lr,
                weight_decay=cfg.weight_decay, grad_clip=cfg.grad_clip)
        else:
            new_params, new_opt, om = adamw_update(
                grads, state["opt"], state["params"], lr,
                weight_decay=cfg.weight_decay, grad_clip=cfg.grad_clip)
        metrics = {"loss": loss, "ce": parts["ce"], "lr": lr, **om}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    return step


# ---------------------------------------------------------------------------
# Policy-aware jitted builders
# ---------------------------------------------------------------------------

def stack_specs(specs: Any) -> Any:
    """Prepend the pod axis to every spec (pod-stacked keygroups)."""
    return jax.tree.map(lambda s: P("pod", *s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def stack_shapes(shapes: Any, n_pods: int) -> Any:
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_pods,) + tuple(l.shape), l.dtype),
        shapes)


def make_train_step(arch: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                    parallel: Optional[ParallelConfig] = None,
                    enoki: Optional[EnokiConfig] = None,
                    cfg: Optional[TrainConfig] = None,
                    impl: AttnImpl = AttnImpl.REFERENCE,
                    donate: bool = True):
    """Returns (jitted_step, state_shape_tree, in_shardings dict).

    Multi-pod behaviour depends on enoki.policy (module docstring).
    """
    parallel = parallel or default_parallel(arch, shape)
    enoki = enoki or EnokiConfig()
    cfg = cfg or TrainConfig()
    multi_pod = "pod" in mesh.shape
    n_pods = mesh.shape.get("pod", 1)

    sshape = state_shapes(arch, parallel)
    step_mesh = mesh if parallel.moe_impl == "ep" and not multi_pod else None
    step = make_step_fn(arch, parallel, cfg, impl, mesh=step_mesh)
    bspecs = batch_specs(arch, shape, mesh, parallel)

    if not multi_pod or enoki.policy == ReplicationPolicy.CLOUD_CENTRAL:
        sspecs = state_specs(sshape, arch, mesh, parallel)
        if multi_pod:  # sync-DP across pods: batch over ("pod","data")
            bspecs = jax.tree.map(
                lambda s: P(("pod", "data") if s and s[0] == "data"
                            else (s[0] if s else None), *s[1:]), bspecs,
                is_leaf=lambda x: isinstance(x, P))
        jitted = jax.jit(step,
                         in_shardings=(named(mesh, sspecs),
                                       named(mesh, bspecs)),
                         out_shardings=(named(mesh, sspecs), None),
                         donate_argnums=(0,) if donate else ())
        return jitted, sshape, (sspecs, bspecs)

    if enoki.policy == ReplicationPolicy.PEER_FETCH:
        sspecs = state_specs(sshape, arch, mesh, parallel,
                             peer_fetch_pod=True)
        bspecs = jax.tree.map(
            lambda s: P(("pod", "data") if s and s[0] == "data"
                        else (s[0] if s else None), *s[1:]), bspecs,
            is_leaf=lambda x: isinstance(x, P))
        jitted = jax.jit(step,
                         in_shardings=(named(mesh, sspecs),
                                       named(mesh, bspecs)),
                         out_shardings=(named(mesh, sspecs), None),
                         donate_argnums=(0,) if donate else ())
        return jitted, sshape, (sspecs, bspecs)

    # REPLICATED: pod-stacked state, vmapped step, no pod collectives
    sspecs = state_specs(sshape, arch, mesh, parallel)
    stacked_specs = stack_specs(sspecs)
    stacked_shape = stack_shapes(sshape, n_pods)
    stacked_bspecs = jax.tree.map(lambda s: P("pod", *s), bspecs,
                                  is_leaf=lambda x: isinstance(x, P))
    vstep = jax.vmap(step)
    jitted = jax.jit(vstep,
                     in_shardings=(named(mesh, stacked_specs),
                                   named(mesh, stacked_bspecs)),
                     out_shardings=(named(mesh, stacked_specs), None),
                     donate_argnums=(0,) if donate else ())
    return jitted, stacked_shape, (stacked_specs, stacked_bspecs)


# ---------------------------------------------------------------------------
# The anti-entropy step (REPLICATED policy, off the hot path)
# ---------------------------------------------------------------------------

def make_replicate_step(arch: ArchConfig, mesh: Mesh,
                        parallel: ParallelConfig, enoki: EnokiConfig,
                        state_shape_stacked: Any):
    """jit((stacked_state, outer_state) -> (stacked_state, outer_state)).

    Pure-jnp anti-entropy: per-pod deltas vs the outer params, optional int8
    wire compression (the cross-pod all-gather then moves 1/4 the bytes —
    visible in the dry-run HLO), mean-merge, DiLoCo outer Nesterov, broadcast
    back into every pod slot.  This program owns ALL pod-axis collectives.
    """
    n_pods = mesh.shape.get("pod", 1)
    sspecs = state_specs(
        jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                     state_shape_stacked),
        arch, mesh, parallel)
    stacked_specs = stack_specs(sspecs)
    outer_specs = {"outer_params": sspecs["params"],
                   "momentum": sspecs["params"], "round": P()}

    def replicate(state, outer_state):
        local = state["params"]                        # (n_pods, ...)
        outer = outer_state["outer_params"]

        if enoki.compress_deltas:
            # int8 ON THE WIRE: quantise per pod, all-gather the int8
            # payload over the pod axis (4× less DCN traffic), dequantise
            # and average locally.  shard_map pins the gather to int8.
            def delta_leaf(o, l):
                def body(o_l, l_l):
                    d = o_l - l_l[0].astype(jnp.float32)
                    q = int8_compress(d)
                    qs = jax.lax.all_gather(q.q, "pod")        # int8 wire
                    ss = jax.lax.all_gather(q.scale, "pod")    # (n_pods,)
                    deq = qs.astype(jnp.float32) * ss.reshape(
                        (n_pods,) + (1,) * d.ndim)
                    return deq.mean(axis=0)
                return jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(P(), P("pod")), out_specs=P(),
                    check_vma=False, axis_names={"pod"})(o, l)
        else:
            def delta_leaf(o, l):
                d = o[None] - l.astype(jnp.float32)    # (n_pods, ...)
                return d.mean(axis=0)                  # pod all-reduce HERE

        mean_delta = jax.tree.map(delta_leaf, outer, local)
        new_outer, new_outer_state = diloco_outer_update(
            outer_state, mean_delta, enoki.outer_lr, enoki.outer_momentum)
        new_params = jax.tree.map(
            lambda no, l: jnp.broadcast_to(no.astype(l.dtype)[None],
                                           l.shape),
            new_outer, local)
        new_state = dict(state)
        new_state["params"] = new_params
        return new_state, new_outer_state

    jitted = jax.jit(replicate,
                     in_shardings=(named(mesh, stacked_specs),
                                   named(mesh, outer_specs)),
                     out_shardings=(named(mesh, stacked_specs),
                                    named(mesh, outer_specs)))
    outer_shape = jax.eval_shape(
        lambda: diloco_init(jax.tree.map(
            lambda l: jnp.zeros(l.shape[1:], l.dtype),
            state_shape_stacked["params"])))
    return jitted, outer_shape, (stacked_specs, outer_specs)
