"""How the fused MRF kernel tiles a grid, in plain Python: the kernel
(`kernels.mrf_gibbs`) and its VMEM estimate (`analysis.kernel_lint`, which
stays free of JAX) both read it from here.

A tile is `tile_rows` rows of the grid, all W columns.  Per label value it
holds an energy plane and a weight plane, plus the KY walk's rejection
plane: (2 V + 1) planes of (rows, W padded to 128 lanes) 4-byte words.
"""

from __future__ import annotations

# Tile rows at most, and the VMEM a tile's per-label planes may take.
MAX_ROWS = 32
TILE_PLANE_BYTES = 8 * 2**20
SUBLANES = 8
LANES = 128


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_plane_bytes(block_h: int, width: int, n_labels: int) -> int:
    """VMEM of a tile's per-label planes: energies, weights, rejection."""
    return (2 * n_labels + 1) * block_h * round_up(width, LANES) * 4


def tile_rows(height: int, width: int, n_labels: int) -> int:
    """A tile's rows: a multiple of 8, at most `MAX_ROWS`, whose per-label
    planes fit `TILE_PLANE_BYTES` (8 rows at least).  Taller tiles walk
    more sites per loop trip, so the tallest is taken whose padding of
    `height` to whole tiles costs at most 1/16 more rows than the least
    padding any of them gives."""
    cap = SUBLANES
    while (cap + SUBLANES <= MAX_ROWS and tile_plane_bytes(
            cap + SUBLANES, width, n_labels) <= TILE_PLANE_BYTES):
        cap += SUBLANES
    rows = range(SUBLANES, min(cap, round_up(height, SUBLANES)) + 1,
                 SUBLANES)
    least = min(round_up(height, bh) for bh in rows)
    return max(bh for bh in rows if 16 * round_up(height, bh) <= 17 * least)
