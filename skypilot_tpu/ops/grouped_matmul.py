"""Grouped matmul for dropless experts: row block ``g`` of ``lhs``
times ``rhs[g]``, the groups laid one after another along the rows.

The kernel is JAX's own Pallas grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``): the group of each row
tile rides the grid as scalar prefetch, so a tile's weight block is a
DMA address into the stacked ``[G, k, n]`` weights and **a group with no
row is never visited: its weights are never read**. That is what a
decode step of a handful of live rows needs from an expert layer (26 of
64 experts at 8 rows x top-4), and what a masked dense loop over every
expert, or a capacity buffer, cannot give. On the CPU the same kernel
runs in interpret mode.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

import jax.experimental.pallas.ops.tpu.megablox.gmm  # noqa: F401
_gmm = sys.modules['jax.experimental.pallas.ops.tpu.megablox.gmm']

# Rows a tile: one MXU pass of a bf16 tile. A group's rows share tiles
# with its neighbours (a tile is visited once per group in it), so a
# larger tile multiplies the masked work where groups are small.
ROW_TILE = 128
# Output columns a tile: with the whole contraction in one block (k <=
# 2048 here) a weight block is k x 512 bf16 = 2 MB, 4 MB double-buffered.
COL_TILE = 512


def row_tile(rows: int) -> int:
    """The row tile for ``rows`` rows: ``ROW_TILE``, or all the rows
    (rounded up to the sublane count) where there are fewer."""
    return ROW_TILE if rows >= ROW_TILE else -(-rows // 8) * 8


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array, *, out_dtype) -> jax.Array:
    """``lhs`` [m, k], rows sorted by group, ``m`` a multiple of
    ``row_tile(m)``; ``rhs`` [G, k, n]; ``group_sizes`` [G] int32 with a
    sum of at most ``m``. Returns [m, n]; rows past the groups' sum
    belong to no group and hold whatever the buffer held (the caller
    selects them away, never multiplies them by zero)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    return _gmm.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                    preferred_element_type=out_dtype,
                    tiling=(row_tile(m), k, min(n, COL_TILE)),
                    interpret=jax.default_backend() != 'tpu')
