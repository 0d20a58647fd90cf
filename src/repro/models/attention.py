"""Attention: GQA/MHA/MQA with a blockwise online-softmax reference path.

The sequence path (train/prefill) is *flash-structured* pure JAX: a
``lax.scan`` over KV blocks with online softmax, so peak memory is
O(S·block) instead of O(S²) while HLO FLOPs remain the true 2·S²·D cost.
On TPU the Pallas kernel (kernels/flash_attention) replaces it 1:1 via
``AttnImpl.FLASH``; on CPU (tests, dry-run) the reference path lowers.

Decode is a single-token gather-free einsum against the full cache — the
memory-bound op the roofline's memory term is dominated by.

Shapes (conventions used across the model zoo):
    x            (B, S, D)
    q            (B, S, H, Dh)
    k, v         (B, S, KV, Dh)
    cache k/v    (B, Smax, KV, Dh)  + scalar ``length`` (tokens filled)
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, AttnImpl
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_init(key, arch: ArchConfig, d_in: Optional[int] = None,
              dtype=jnp.float32) -> dict:
    d = d_in or arch.d_model
    dh = arch.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, arch.num_heads * dh), dtype=dtype),
        "wk": dense_init(ks[1], (d, arch.num_kv_heads * dh), dtype=dtype),
        "wv": dense_init(ks[2], (d, arch.num_kv_heads * dh), dtype=dtype),
        "wo": dense_init(ks[3], (arch.num_heads * dh, arch.d_model), dtype=dtype),
    }
    if arch.qkv_bias:
        p["bq"] = jnp.zeros((arch.num_heads * dh,), dtype)
        p["bk"] = jnp.zeros((arch.num_kv_heads * dh,), dtype)
        p["bv"] = jnp.zeros((arch.num_kv_heads * dh,), dtype)
    return p


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _project_qkv(params: dict, xq: jnp.ndarray, xkv: jnp.ndarray,
                 arch: ArchConfig):
    dh = arch.resolved_head_dim
    q = xq @ params["wq"]
    k = xkv @ params["wk"]
    v = xkv @ params["wv"]
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    B, Sq = xq.shape[:2]
    Skv = xkv.shape[1]
    q = q.reshape(B, Sq, arch.num_heads, dh)
    k = k.reshape(B, Skv, arch.num_kv_heads, dh)
    v = v.reshape(B, Skv, arch.num_kv_heads, dh)
    return q, k, v


# ---------------------------------------------------------------------------
# Blockwise online-softmax attention (the flash-structured reference)
# ---------------------------------------------------------------------------

def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        q_positions: jnp.ndarray, kv_positions: jnp.ndarray,
                        causal: bool = True, window: int = 0,
                        kv_block: int = 512) -> jnp.ndarray:
    """Online-softmax attention scanned over KV blocks.

    q (B,Sq,H,Dh); k,v (B,Skv,KV,Dh); positions (B,S) int32.
    GQA handled by grouping: H = KV * G, scores computed per (KV, G) pair so
    K/V are never materialised per query head.
    window > 0 restricts attention to the last ``window`` positions
    (sliding-window; used by zamba2's shared block in long mode).
    """
    B, Sq, H, Dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = Dh ** -0.5
    blk = min(kv_block, Skv)
    while Skv % blk:                      # static; shapes are powers of two here
        blk //= 2
    nblk = Skv // blk

    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, KV, G, Dh)
    kb = k.reshape(B, nblk, blk, KV, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, blk, KV, Dh).transpose(1, 0, 2, 3, 4)
    pb = kv_positions.reshape(B, nblk, blk).transpose(1, 0, 2)

    def step(carry, xs):
        m, l, acc = carry
        k_blk, v_blk, p_blk = xs            # (B,blk,KV,Dh), (B,blk)
        # bf16 operands, f32 accumulation: no f32 copy of K/V is ever made
        s = jnp.einsum("bqkgd,bskd->bqkgs", qg, k_blk,
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((B, Sq, 1, 1, blk), bool)
        if causal:
            mask &= (q_positions[:, :, None, None, None]
                     >= p_blk[:, None, None, None, :])
        if window > 0:
            mask &= (q_positions[:, :, None, None, None]
                     - p_blk[:, None, None, None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgs,bskd->bqkgd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def qscan_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    q_positions: jnp.ndarray, kv_positions: jnp.ndarray,
                    causal: bool = True, window: int = 0,
                    q_block: int = 512) -> jnp.ndarray:
    """Scan over QUERY blocks with a full-row one-pass softmax.

    Versus the kv-block scan, nothing f32 is carried across steps — the
    (B,S,H,Dh) f32 accumulator read-modify-writes disappear (§Perf iter 4).
    K/V stay resident (bf16, ~100 MB/device at assigned shapes); per-step
    live memory is one (B, bq, H, Skv) f32 score block."""
    B, Sq, H, Dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    blk = min(q_block, Sq)
    while Sq % blk:
        blk //= 2
    nblk = Sq // blk
    qg = (q * jnp.asarray(Dh ** -0.5, q.dtype)).reshape(B, nblk, blk, KV, G,
                                                        Dh).transpose(
        1, 0, 2, 3, 4, 5)
    pq = q_positions.reshape(B, nblk, blk).transpose(1, 0, 2)

    def step(_, xs):
        q_blk, p_blk = xs                    # (B,blk,KV,G,Dh), (B,blk)
        s = jnp.einsum("bqkgd,bskd->bqkgs", q_blk, k,
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((B, blk, 1, 1, Skv), bool)
        if causal:
            mask &= (p_blk[:, :, None, None, None]
                     >= kv_positions[:, None, None, None, :])
        if window > 0:
            mask &= (p_blk[:, :, None, None, None]
                     - kv_positions[:, None, None, None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return 0, out.astype(q.dtype)

    _, outs = jax.lax.scan(step, 0, (qg, pq))      # (nblk,B,blk,KV,G,Dh)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dh)


def reference_attention(q, k, v, q_positions, kv_positions, causal=True,
                        window: int = 0) -> jnp.ndarray:
    """O(S²)-memory oracle used only by tests at tiny shapes."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg, k.astype(jnp.float32))
    s = s * (Dh ** -0.5)
    mask = jnp.ones((B, Sq, 1, 1, k.shape[1]), bool)
    if causal:
        mask &= (q_positions[:, :, None, None, None]
                 >= kv_positions[:, None, None, None, :])
    if window > 0:
        mask &= (q_positions[:, :, None, None, None]
                 - kv_positions[:, None, None, None, :]) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Sequence-mode self-attention (train / prefill)
# ---------------------------------------------------------------------------

def self_attention(params: dict, x: jnp.ndarray, positions: jnp.ndarray,
                   arch: ArchConfig, causal: bool = True, window: int = 0,
                   impl: AttnImpl = AttnImpl.REFERENCE,
                   kv_block: int = 512) -> jnp.ndarray:
    q, k, v = _project_qkv(params, x, x, arch)
    if arch.rope_theta > 0:
        q = apply_rope(q, positions, arch.rope_theta)
        k = apply_rope(k, positions, arch.rope_theta)
    if impl == AttnImpl.FLASH:
        from repro.kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=causal, window=window)
    elif impl == AttnImpl.QSCAN:
        out = qscan_attention(q, k, v, positions, positions, causal=causal,
                              window=window)
    else:
        out = blockwise_attention(q, k, v, positions, positions,
                                  causal=causal, window=window,
                                  kv_block=kv_block)
    B, S = x.shape[:2]
    return out.reshape(B, S, -1) @ params["wo"]


def cross_attention(params: dict, x: jnp.ndarray, kv_cache_k: jnp.ndarray,
                    kv_cache_v: jnp.ndarray, arch: ArchConfig) -> jnp.ndarray:
    """Decoder->encoder cross-attention against precomputed K/V (whisper)."""
    B, Sq = x.shape[:2]
    dh = arch.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, Sq, arch.num_heads, dh)
    if "bq" in params:
        q = q + params["bq"].reshape(arch.num_heads, dh).astype(q.dtype)
    Skv = kv_cache_k.shape[1]
    pos_q = jnp.zeros((B, Sq), jnp.int32)
    pos_kv = jnp.zeros((B, Skv), jnp.int32)
    out = blockwise_attention(q, kv_cache_k, kv_cache_v, pos_q, pos_kv,
                              causal=False)
    return out.reshape(B, Sq, -1) @ params["wo"]


def project_cross_kv(params: dict, enc_out: jnp.ndarray, arch: ArchConfig):
    """K/V of the encoder output, computed once at prefill (whisper)."""
    B, S = enc_out.shape[:2]
    dh = arch.resolved_head_dim
    k = (enc_out @ params["wk"]).reshape(B, S, arch.num_kv_heads, dh)
    v = (enc_out @ params["wv"]).reshape(B, S, arch.num_kv_heads, dh)
    if "bk" in params:
        k = k + params["bk"].reshape(arch.num_kv_heads, dh).astype(k.dtype)
        v = v + params["bv"].reshape(arch.num_kv_heads, dh).astype(v.dtype)
    return k, v


# ---------------------------------------------------------------------------
# Flash decode: partial softmax over the sequence-sharded cache (§Perf B)
# ---------------------------------------------------------------------------

def flash_decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray,
                           cache_v: jnp.ndarray, length, mesh,
                           axis: str = "model") -> jnp.ndarray:
    """Decode attention with the cache sharded on the SEQUENCE dim.

    Baseline XLA propagation re-gathers the whole cache to softmax over the
    full sequence (the 'involuntary full rematerialization' warnings and the
    dominant decode memory+collective term).  Here each shard computes a
    partial softmax over its local S/n slice and the shards combine with
    three tiny collectives (pmax of the max, psum of the normaliser and of
    the weighted values) — flash-decode, expressed in shard_map.

    q (B,1,KV,G,Dh) f32-scaled not required; cache (B,S,KV,Dh) sharded on S.
    Returns (B,1,KV,G,Dh) f32, replicated over `axis`.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    S = cache_k.shape[1]
    s_local = S // n

    def body(qb, ck, cv, ln):
        shard = jax.lax.axis_index(axis)
        base = shard * s_local
        s = jnp.einsum("bqkgd,bskd->bqkgs", qb, ck,
                       preferred_element_type=jnp.float32)
        idx = base + jnp.arange(s_local)
        s = jnp.where((idx <= ln)[None, None, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)                                   # (B,1,KV,G)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        o_g = jax.lax.psum(o * corr[..., None], axis)
        return o_g / jnp.maximum(l_g, 1e-30)[..., None]

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None),
                  P()),
        out_specs=P(), check_vma=False, axis_names={axis},
    )(q, cache_k, cache_v, length)


def decode_self_attention_sharded(params: dict, x1: jnp.ndarray,
                                  cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                                  length, arch: ArchConfig, mesh
                                  ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                             jnp.ndarray]:
    """decode_self_attention with the flash-decode read path."""
    B = x1.shape[0]
    dh = arch.resolved_head_dim
    pos = jnp.broadcast_to(length, (B, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(params, x1, x1, arch)
    if arch.rope_theta > 0:
        q = apply_rope(q, pos, arch.rope_theta)
        k = apply_rope(k, pos, arch.rope_theta)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, length, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, length, 0, 0))
    KV = cache_k.shape[2]
    G = arch.num_heads // KV
    qg = (q * jnp.asarray(dh ** -0.5, q.dtype)).reshape(B, 1, KV, G, dh)
    out = flash_decode_attention(qg, cache_k, cache_v, length, mesh)
    out = out.reshape(B, 1, -1).astype(x1.dtype) @ params["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# Decode mode (one token, KV cache)
# ---------------------------------------------------------------------------

def decode_self_attention(params: dict, x1: jnp.ndarray, cache_k: jnp.ndarray,
                          cache_v: jnp.ndarray, length: jnp.ndarray,
                          arch: ArchConfig, window: int = 0
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step.  x1 (B,1,D); cache (B,Smax,KV,Dh); length scalar.

    Returns (attn_out (B,1,D), cache_k', cache_v').
    """
    B = x1.shape[0]
    dh = arch.resolved_head_dim
    pos = jnp.broadcast_to(length, (B, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(params, x1, x1, arch)
    if arch.rope_theta > 0:
        q = apply_rope(q, pos, arch.rope_theta)
        k = apply_rope(k, pos, arch.rope_theta)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, length, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, length, 0, 0))

    Smax, KV = cache_k.shape[1], cache_k.shape[2]
    G = arch.num_heads // KV
    qg = (q * jnp.asarray(dh ** -0.5, q.dtype)).reshape(B, 1, KV, G, dh)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg, cache_k,
                   preferred_element_type=jnp.float32)
    idx = jnp.arange(Smax)
    valid = idx <= length
    if window > 0:
        valid &= idx > length - window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(cache_v.dtype), cache_v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, -1).astype(x1.dtype) @ params["wo"]
    return out, cache_k, cache_v
