"""Enoki versioned merge kernel, Pallas TPU — the paper-specific hot spot.

Anti-entropy over replicated arenas reduces to one elementwise-ish
primitive: *versioned last-writer-wins select* over (value, version)
pairs, slot-aligned:

    out_val[i]  = b_val[i]  if b_ver[i] > a_ver[i] else a_val[i]
    out_ver[i]  = max(a_ver[i], b_ver[i])

where one version guards a row of V payload elements (the arena layout of
core/store.py).  The op is bandwidth-bound; the kernel streams both
replicas through VMEM in (rows × V) tiles.

Layout.  Versions travel LANE-DENSE, as a (rows/128, 128) view: Mosaic
refuses to broadcast a 1-D (rows,) block across lanes, and an (R, 1)
column would pad every version to a 128-lane row in HBM.  Inside the
kernel one transpose turns the tile's compare results into (128, 1)
sublane columns, one per 128-row group, and each column drives the row
select of its group.

Tiling.  An arena that fits one values tile of about ``_TILE_BYTES`` is
merged as a single block of any slot count (only its versions are padded
to whole 128-lane rows).  A larger arena is cut into tiles of a multiple
of 1024 rows (8 sublanes of versions); a slot count that is not a
multiple of that tile is zero-padded to it, which costs a copy of the
arena per merge.  Padding rows never win: equal versions keep ``a``.
The payload width V is never padded: a block spans the full width.  A
width so wide that a 1024-row tile overruns ``MAX_VMEM_BYTES`` is
refused (``check_merge_width``, also run when a keygroup is declared);
below that, the kernel asks for the scoped VMEM its tile needs.

Platform.  ``interpret=None`` picks by the platform the call is lowered
for: the Mosaic kernel on a TPU, the Pallas interpreter elsewhere, so an
ahead-of-time compile for a TPU gets the kernel even from a CPU host.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_QUANTUM = 8 * LANES            # rows of one (8, 128) tile of versions
_TILE_BYTES = 1 << 20           # target VMEM bytes of one values tile
_VMEM_DEFAULT = 16 << 20        # the v5e's default scoped VMEM limit
MAX_VMEM_BYTES = 64 << 20       # half of a v5e core's 128 MiB of VMEM


def _lane_width(width: int) -> int:
    # VMEM holds a row in whole 128-lane vregs
    return -(-width // LANES) * LANES


def merge_geometry(rows: int, width: int, itemsize: int):
    """``(padded_rows, rows_tile)`` of an (rows, width) arena under
    ``enoki_merge_rows``.  The tile budget counts the lane-padded width."""
    lane_w = _lane_width(width)
    cap = max(_QUANTUM,
              _TILE_BYTES // (lane_w * itemsize) // _QUANTUM * _QUANTUM)
    if rows <= cap:
        return rows, rows
    return -(-rows // cap) * cap, cap


def merge_vmem_bytes(rows: int, width: int, itemsize: int) -> int:
    """Scoped VMEM ``enoki_merge_rows`` needs at this geometry: the a, b
    and out blocks of values and versions, each double-buffered, plus one
    128-row group's loads and select result inside the kernel."""
    _, rt = merge_geometry(rows, width, itemsize)
    row_bytes = _lane_width(width) * itemsize
    ver_bytes = -(-rt // _QUANTUM) * _QUANTUM * 4
    return 2 * 3 * (rt * row_bytes + ver_bytes) + 3 * LANES * row_bytes


def check_merge_width(width: int, dtype) -> None:
    """Refuse a payload width whose widest row tile (1024 rows, reached
    by any arena of more rows) needs more than ``MAX_VMEM_BYTES``."""
    need = merge_vmem_bytes(_QUANTUM + 1, width, jnp.dtype(dtype).itemsize)
    if need > MAX_VMEM_BYTES:
        raise ValueError(
            f"value_width={width} of {jnp.dtype(dtype).name} is too wide "
            f"for the replica merge: a {_QUANTUM}-row tile needs {need} "
            f"bytes of VMEM, more than {MAX_VMEM_BYTES}")


def _merge_kernel(av_ref, aver_ref, bv_ref, bver_ref, ov_ref, over_ref):
    a_ver = aver_ref[...]                       # (groups, 128) lane-dense
    b_ver = bver_ref[...]
    over_ref[...] = jnp.maximum(a_ver, b_ver)
    take_t = (b_ver > a_ver).astype(jnp.float32).T      # (128, groups)
    rows = ov_ref.shape[0]
    for g in range(take_t.shape[1]):
        n = min(LANES, rows - g * LANES)
        take = take_t[:n, g:g + 1] > 0.5        # (n, 1) sublane column
        sl = pl.ds(g * LANES, n)
        ov_ref[sl, :] = jnp.where(take, bv_ref[sl, :], av_ref[sl, :])


def enoki_merge_rows(a_val, a_ver, b_val, b_ver, *,
                     interpret: Optional[bool] = None):
    """a_val/b_val (R, V); a_ver/b_ver (R,) int32 packed versions.
    Returns (merged_val (R, V), merged_ver (R,)).  ``interpret=None``
    chooses by the platform lowered for (module docstring)."""
    check_merge_width(a_val.shape[1], a_val.dtype)
    if interpret is None:
        return jax.lax.platform_dependent(
            a_val, a_ver, b_val, b_ver,
            tpu=functools.partial(_merge_rows, interpret=False),
            default=functools.partial(_merge_rows, interpret=True))
    return _merge_rows(a_val, a_ver, b_val, b_ver, interpret=interpret)


def _merge_rows(a_val, a_ver, b_val, b_ver, *, interpret: bool):
    R, V = a_val.shape
    itemsize = a_val.dtype.itemsize
    Rp, rt = merge_geometry(R, V, itemsize)
    vmem = merge_vmem_bytes(R, V, itemsize)
    if Rp != R:
        pad = ((0, Rp - R), (0, 0))
        a_val, b_val = jnp.pad(a_val, pad), jnp.pad(b_val, pad)
    groups = -(-Rp // LANES)
    ver_pad = (0, groups * LANES - R)
    a_ver2 = jnp.pad(a_ver, ver_pad).reshape(groups, LANES)
    b_ver2 = jnp.pad(b_ver, ver_pad).reshape(groups, LANES)
    val_spec = pl.BlockSpec((rt, V), lambda i: (i, 0))
    ver_spec = pl.BlockSpec((-(-rt // LANES), LANES), lambda i: (i, 0))
    out_val, out_ver = pl.pallas_call(
        _merge_kernel,
        grid=(Rp // rt,),
        in_specs=[val_spec, ver_spec, val_spec, ver_spec],
        out_specs=[val_spec, ver_spec],
        out_shape=[jax.ShapeDtypeStruct((Rp, V), a_val.dtype),
                   jax.ShapeDtypeStruct(a_ver2.shape, a_ver.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem if vmem > _VMEM_DEFAULT else None),
        interpret=interpret,
        name="enoki_merge_rows",
    )(a_val, a_ver2, b_val, b_ver2)
    return out_val[:R], out_ver.reshape(-1)[:R]
