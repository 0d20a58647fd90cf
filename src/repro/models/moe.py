"""Mixture-of-Experts layer: top-k routing with sort-based dispatch.

Dispatch never materialises a (tokens × experts) tensor: assignments are
sorted by expert id, positions-within-expert computed from per-expert offsets,
and tokens scattered into a fixed-capacity (E, C, D) bucket tensor (capacity
overflow drops, as in Switch/GShard).  This is the shape EP sharding wants:
bucket/expert tensors are sharded on E over the ``model`` axis (kimi-k2,
384 experts → 24/shard) and XLA inserts the all-to-all at the scatter/gather.
Few-big-expert models (grok-1, 8 experts < 16 shards) instead shard each
expert's FFN dim over ``model`` (tensor-parallel experts, E replicated) —
``expert_sharding_strategy`` picks per arch×mesh.

The router runs in float32; an auxiliary load-balancing loss (Switch-style
fraction·probability product) is returned for the training objective.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import Activation, ArchConfig, MoEConfig
from repro.models.layers import dense_init, gated_mlp


def moe_init(key, arch: ArchConfig, dtype=jnp.float32) -> dict:
    cfg = arch.moe
    d, f, e = arch.d_model, cfg.d_expert, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), scale=d ** -0.5, dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, f), dtype=dtype),
        "w_down": dense_init(ks[3], (e, f, d), dtype=dtype),
    }
    if cfg.shared_expert:
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(ks[4], d, f, Activation.SWIGLU, dtype=dtype)
    return p


def capacity(tokens: int, cfg: MoEConfig, multiple: int = 128) -> int:
    """Static per-expert bucket capacity, padded to ``multiple`` (128 = MXU
    tile for sequence mode; decode uses 8 to avoid padding FLOPs at tiny
    per-expert batch)."""
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(multiple, ((c + multiple - 1) // multiple) * multiple)


def route(router_w: jnp.ndarray, x: jnp.ndarray, cfg: MoEConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing.  x (T, D) -> (expert_idx (T,k), weight (T,k), aux_loss)."""
    logits = x.astype(jnp.float32) @ router_w                  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weight, expert_idx = jax.lax.top_k(probs, cfg.top_k)       # (T, k)
    weight = weight / jnp.maximum(weight.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: E * Σ_e fraction_e * mean_prob_e
    e = cfg.num_experts
    fraction = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0) / (x.shape[0] * cfg.top_k)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(fraction * mean_prob)
    return expert_idx, weight.astype(x.dtype), aux


def dispatch_indices(expert_idx: jnp.ndarray, num_experts: int, cap: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bucket slot for each (token,k) assignment via sort-based ranking.

    Returns (slot (A,), kept (A,)) where A = T*k and slot = e*cap + rank of
    the assignment within expert e (rank >= cap -> dropped).
    """
    flat = expert_idx.reshape(-1)                              # (A,)
    a = flat.shape[0]
    order = jnp.argsort(flat, stable=True)                     # tokens grouped by expert
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    sorted_e = flat[order]
    rank_sorted = jnp.arange(a, dtype=jnp.int32) - starts[sorted_e]
    rank = jnp.zeros((a,), jnp.int32).at[order].set(rank_sorted)
    kept = rank < cap
    slot = jnp.where(kept, flat * cap + rank, num_experts * cap)  # OOB == drop
    return slot, kept


def moe_apply(params: dict, x: jnp.ndarray, arch: ArchConfig,
              cap_multiple: int = 128) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (y (B, S, D), aux_loss).  Pure; pjit-shardable."""
    cfg = arch.moe
    B, S, D = x.shape
    t = B * S
    xt = x.reshape(t, D)
    expert_idx, weight, aux = route(params["router"], xt, cfg)
    cap = capacity(t, cfg, cap_multiple)
    slot, kept = dispatch_indices(expert_idx, cfg.num_experts, cap)

    # scatter tokens (duplicated per k) into buckets; drops fall off the end
    a = t * cfg.top_k
    token_of = jnp.repeat(jnp.arange(t, dtype=jnp.int32), cfg.top_k)
    buckets = jnp.zeros((cfg.num_experts * cap, D), x.dtype)
    buckets = buckets.at[slot].set(xt[token_of], mode="drop")
    buckets = buckets.reshape(cfg.num_experts, cap, D)

    # expert FFN: grouped einsum over the expert dim
    h_gate = jnp.einsum("ecd,edf->ecf", buckets, params["w_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", buckets, params["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    y_buckets = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    # gather back and combine with routing weights
    y_flat = y_buckets.reshape(cfg.num_experts * cap, D)
    gathered = jnp.where(kept[:, None], y_flat.at[slot].get(mode="fill",
                                                            fill_value=0), 0)
    contrib = gathered * weight.reshape(a, 1).astype(gathered.dtype)
    y = jnp.zeros((t, D), x.dtype).at[token_of].add(contrib.astype(x.dtype))

    if cfg.shared_expert:
        y = y + gated_mlp(params["shared"], xt, Activation.SWIGLU)
    return y.reshape(B, S, D), aux * cfg.aux_loss_weight


def expert_sharding_strategy(cfg: MoEConfig, model_shards: int) -> str:
    """'ep' — shard E over model (E % shards == 0); 'tp' — shard d_expert."""
    if cfg.num_experts % model_shards == 0:
        return "ep"
    return "tp"


# ---------------------------------------------------------------------------
# Explicit expert-parallel dispatch (shard_map) — the §Perf MoE fix
# ---------------------------------------------------------------------------
#
# The pjit/auto path above leaves dispatch locality to XLA's SPMD propagation,
# which all-gathers the full token array to every expert shard (measured:
# the dominant collective AND memory term for grok/kimi — EXPERIMENTS §Perf).
# Here the structure is explicit: routing is computed globally (cheap), then
# inside a manual ("data","model") shard_map each model column selects ONLY
# the assignments that hit its local experts from its data shard's tokens,
# computes them, and the columns combine with one psum — the same wire cost
# as a dense TP MLP layer.

def moe_apply_ep(params: dict, x: jnp.ndarray, arch: ArchConfig, mesh,
                 cap_multiple: int = 128) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE.  Requires E % mesh['model'] == 0 and
    (B·S) % mesh['data'] == 0; callers fall back to ``moe_apply`` otherwise.
    """
    from jax.sharding import PartitionSpec as P

    cfg = arch.moe
    B, S, D = x.shape
    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"]
    e_local = cfg.num_experts // n_model
    t = B * S
    t_local = t // n_data
    cap = capacity(t_local, cfg, cap_multiple)

    xt = x.reshape(t, D)
    expert_idx, weight, aux = route(params["router"], xt, cfg)

    def body(xt_l, eidx_l, wgt_l, wg, wu, wd):
        col = jax.lax.axis_index("model")
        lo = col * e_local
        rel = eidx_l - lo
        valid = (rel >= 0) & (rel < e_local)
        eff = jnp.where(valid, rel, e_local).reshape(-1)     # trash bucket
        slot, kept = dispatch_indices(eff, e_local + 1, cap)
        kept &= valid.reshape(-1)
        a = t_local * cfg.top_k
        token_of = jnp.repeat(jnp.arange(t_local, dtype=jnp.int32),
                              cfg.top_k)
        buckets = jnp.zeros((e_local * cap, D), xt_l.dtype)
        buckets = buckets.at[slot].set(xt_l[token_of], mode="drop")
        buckets = buckets.reshape(e_local, cap, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buckets, wg)) \
            * jnp.einsum("ecd,edf->ecf", buckets, wu)
        yb = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_local * cap, D)
        # combine in the bucket domain: one scatter-add from (E·C, D), no
        # (T·k, D) intermediate (§Perf iteration 3)
        nslots = e_local * cap
        token_by_slot = jnp.full((nslots,), t_local, jnp.int32).at[slot].set(
            token_of, mode="drop")                       # OOB rows drop below
        w_by_slot = jnp.zeros((nslots,), yb.dtype).at[slot].set(
            (wgt_l.reshape(a) * kept).astype(yb.dtype), mode="drop")
        y = jnp.zeros((t_local, D), xt_l.dtype).at[token_by_slot].add(
            (yb * w_by_slot[:, None]).astype(xt_l.dtype), mode="drop")
        return jax.lax.psum(y, "model")

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P("data", None), P("data", None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P("data", None), check_vma=False,
        axis_names={"data", "model"},
    )(xt, expert_idx, weight, params["w_gate"], params["w_up"],
      params["w_down"])

    if cfg.shared_expert:
        y = y + gated_mlp(params["shared"], xt, Activation.SWIGLU)
    return y.reshape(B, S, D), aux * cfg.aux_loss_weight
