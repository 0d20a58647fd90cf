"""Mamba-2 SSD chunk kernel, Pallas TPU.

One grid step = one (batch, head, chunk) cell.  The chunk dimension is the
innermost, "arbitrary" axis: the (P × N) recurrent state lives in VMEM
scratch and flows across chunk iterations — the inter-chunk recurrence is
sequential per (b, h), exactly the dependency structure of the SSD
algorithm, while (b, h) parallelise across cores.

Per chunk (l = chunk length, p = head dim, n = state dim):
  intra:  Y_diag = ((C Bᵀ) ⊙ L) · (dt·X)         two (l×n)(n×l) + (l×l)(l×p)
  inter:  Y_off  = (C · state) ⊙ exp(A_cum)
  state' = state·exp(A_sum) + (B ⊙ decay)ᵀ (dt·X)

VMEM working set ≈ l·(2n + 2p) + l² + p·n floats; defaults (l=128, p=64,
n=64) ≈ 200 kB.  All matmul dims are 64/128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, adt_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (l, p)  already dt-weighted
    a = adt_ref[0, 0].astype(jnp.float32)        # (l,)
    b = b_ref[0, 0].astype(jnp.float32)          # (l, n)
    c = c_ref[0, 0].astype(jnp.float32)          # (l, n)

    a_cum = jnp.cumsum(a)                        # (l,)
    # intra-chunk: L[i,j] = exp(a_cum[i] - a_cum[j]) for j <= i
    seg = a_cum[:, None] - a_cum[None, :]
    tril = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tril, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(scores * L, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                       # (p, n)
    y_off = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_diag + y_off * jnp.exp(a_cum)[:, None]).astype(
        y_ref.dtype)

    # state update
    decay_to_end = jnp.exp(a_cum[-1] - a_cum)    # (l,)
    bw = b * decay_to_end[:, None]               # (l, n)
    new = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (p, n)
    state_ref[...] = state * jnp.exp(a_cum[-1]) + new


def ssd_chunk_bhcp(x, a_dt, b, c, *, chunk: int = 128,
                   interpret: bool = False):
    """x (B,H,S,P) dt-weighted input; a_dt (B,H,S); b,c (B,1,S,N) shared
    across heads (n_groups=1) -> y (B,H,S,P)."""
    B, H, S, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bb, h, i: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bb, h, i: (bb, h, i)),
            pl.BlockSpec((1, 1, chunk, N), lambda bb, h, i: (bb, 0, i, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bb, h, i: (bb, 0, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P),
                               lambda bb, h, i: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, a_dt, b, c)
