"""Bytes a kernel must move per call, computed from its shapes.

``enoki_merge_rows`` merges two replicas row by row: it reads both value
blocks and both version vectors and writes one of each.  Above one tile of
about 1 MiB of values the rows are padded to whole tiles of a multiple of
1024 rows (the kernel's own geometry, restated here so that the yardstick
does not move with the program); the versions travel as whole 128-lane
rows.
"""
from __future__ import annotations

LANES = 128
QUANTUM = 8 * LANES
TILE_BYTES = 1 << 20


def merge_rows_padded(rows: int, width: int, itemsize: int) -> int:
    lane_w = -(-width // LANES) * LANES
    cap = max(QUANTUM, TILE_BYTES // (lane_w * itemsize) // QUANTUM * QUANTUM)
    return rows if rows <= cap else -(-rows // cap) * cap


def merge_bytes(rows: int, width: int, itemsize: int) -> int:
    """HBM bytes of one call: 3 value blocks and 3 version rows."""
    rp = merge_rows_padded(rows, width, itemsize)
    ver = -(-rp // LANES) * LANES * 4
    return 3 * rp * width * itemsize + 3 * ver
