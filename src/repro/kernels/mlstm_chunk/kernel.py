"""mLSTM chunkwise kernel, Pallas TPU (xLSTM matrix memory).

Grid (B, H, num_chunks); chunk axis innermost/"arbitrary" with the
(dqk × dv) matrix state C, normaliser n (dqk,) and stabiliser m ()
in VMEM scratch, carried across chunk iterations.

Stabilised log-space math identical to models/xlstm._mlstm_chunk_parallel
(the oracle): intra-chunk decay matrix D from cumulative log-f + log-i,
running-max stabiliser, |denominator| ≥ exp(−m) guard.

VMEM per step ≈ l·(2dqk+dv) + l² + dqk·dv floats; defaults (l=64,
dqk=dv=512) ≈ 1.3 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, y_ref,
                  c_ref, n_ref, m_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    q = q_ref[0, 0].astype(jnp.float32)       # (l, dqk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)       # (l, dv)
    log_i = i_ref[0, 0].astype(jnp.float32)   # (l,)
    log_f = f_ref[0, 0].astype(jnp.float32)
    scale = q.shape[-1] ** -0.5
    m_prev = m_ref[0]
    C_prev = c_ref[...]
    n_prev = n_ref[...]

    b = jnp.cumsum(log_f)                     # (l,)
    D = b[:, None] - b[None, :] + log_i[None, :]
    l_ = q.shape[0]
    tril = jax.lax.broadcasted_iota(jnp.int32, (l_, l_), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (l_, l_), 1)
    D = jnp.where(tril, D, NEG_INF)
    m_intra = D.max(axis=1)
    m_inter = b + m_prev
    m_tot = jnp.maximum(m_intra, m_inter)

    S = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    W = S * jnp.exp(D - m_tot[:, None])
    h_intra = jax.lax.dot_general(W, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    dec_in = jnp.exp(m_inter - m_tot)
    qs = q * scale
    h_inter = jax.lax.dot_general(qs, C_prev, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32) \
        * dec_in[:, None]
    norm = W.sum(axis=1) + (qs @ n_prev) * dec_in
    denom = jnp.maximum(jnp.abs(norm), jnp.exp(-m_tot))
    y_ref[0, 0] = ((h_intra + h_inter) / denom[:, None]).astype(y_ref.dtype)

    # carry to end of chunk
    m_next = jnp.maximum(b[-1] + m_prev, (b[-1] - b + log_i).max())
    dec_c = jnp.exp(b[-1] + m_prev - m_next)
    w_kv = jnp.exp(b[-1] - b + log_i - m_next)          # (l,)
    kw = k * w_kv[:, None]
    c_ref[...] = C_prev * dec_c + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_ref[...] = n_prev * dec_c + kw.sum(axis=0)
    m_ref[0] = m_next


def mlstm_chunk_bhsd(q, k, v, log_i, log_f, *, chunk: int = 64,
                     interpret: bool = False):
    """q/k/v (B,H,S,d); log_i/log_f (B,H,S) -> h (B,H,S,d)."""
    B, H, S, d = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    spec4 = pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, i: (b_, h_, i, 0))
    spec3 = pl.BlockSpec((1, 1, chunk), lambda b_, h_, i: (b_, h_, i))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[spec4, spec4, spec4, spec3, spec3],
        out_specs=spec4,
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((d,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_i, log_f)
