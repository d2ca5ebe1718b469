"""The TPU tiling rule shared by the vertex-blocked Pallas kernels.

XLA lays a 1-D int32 (or bool) vector out on the TPU in tiles of 1024
elements — one (8, 128) vreg.  Mosaic refuses a kernel whose 1-D block is
not a whole number of those tiles ("XLA layout T(1024) does not match
Mosaic layout T(256)"), so every vertex block is rounded up to whole
tiles.  A block that covers the whole vector is always legal, which keeps
small graphs a single grid step.
"""
from __future__ import annotations

VERTEX_TILE = 1024


def vertex_block(block_v: int, n: int) -> int:
    """The vertex block a kernel uses for ``n`` vertices when
    ``block_v`` is requested: rounded up to whole tiles, at most ``n``."""
    return min(-(-max(block_v, 1) // VERTEX_TILE) * VERTEX_TILE, n)
