"""Jit'd wrapper: versioned merge over arenas and flat tensor keygroups."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.enoki_merge.kernel import enoki_merge_rows


@functools.partial(jax.jit, static_argnames=("interpret",))
def enoki_merge(a_val, a_ver, b_val, b_ver, *,
                interpret: Optional[bool] = None):
    return enoki_merge_rows(a_val, a_ver, b_val, b_ver, interpret=interpret)


def merge_flat_keygroup(a_flat: jnp.ndarray, a_ver: jnp.ndarray,
                        b_flat: jnp.ndarray, b_ver: jnp.ndarray,
                        row_width: int = 1024,
                        interpret: Optional[bool] = None):
    """LWW-merge two flat replicas (N,) with per-row versions.

    Row-granularity contract: versions guard ``row_width`` payload
    elements each, so a replica of N elements carries
    ``ceil(N / row_width)`` version entries — the LAST one owning the
    ragged tail when ``row_width`` does not divide N.  Used for large
    tensor keygroups where per-element versions would double the state
    size.  Returns ``(merged (N,), merged versions (ceil(N/row_width),))``
    — the tail's version entry is merged (elementwise max of the winning
    compare) exactly like the full rows', not dropped.
    """
    n = a_flat.shape[0]
    rows = n // row_width
    full = rows * row_width
    assert a_ver.shape[0] == b_ver.shape[0] == rows + (1 if full < n else 0), \
        (a_ver.shape, b_ver.shape, n, row_width)
    if rows:
        va, vb = (a_flat[:full].reshape(rows, row_width),
                  b_flat[:full].reshape(rows, row_width))
        out, mver = enoki_merge(va, a_ver[:rows], vb, b_ver[:rows],
                                interpret=interpret)
        out = out.reshape(-1)
    else:
        out, mver = a_flat[:0], a_ver[:0]
    if full < n:   # ragged tail: one versioned row, jnp fallback
        tail_take_b = b_ver[rows] > a_ver[rows]
        tail = jnp.where(tail_take_b, b_flat[full:], a_flat[full:])
        out = jnp.concatenate([out, tail])
        mver = jnp.concatenate(
            [mver, jnp.maximum(a_ver[rows:], b_ver[rows:])])
    return out, mver
