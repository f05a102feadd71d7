"""Jit'd public wrappers gluing the Pallas kernel to the algorithm layer.

``build_group_block_mask`` converts the algorithmic per-(point, group)
filter decisions into the block-granular skip mask ``grouped_assign``
consumes — the exact point where KPynq's per-point pipeline bypass
becomes the TPU's block bypass. ``compact_indices`` is the beyond-paper
stream-compaction alternative (gather survivors into dense tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .grouped_assign import grouped_assign

__all__ = ["build_group_block_mask", "compact_indices", "grouped_assign"]


@functools.partial(jax.jit, static_argnames=("tile_n",))
def build_group_block_mask(group_need: jnp.ndarray, *,
                           tile_n: int) -> jnp.ndarray:
    """(N, G) per-point-per-group need -> (ceil(N/tile_n), G) bool mask
    for the group-granular kernel (``grouped_assign``): block (i, g) is
    live iff any point in tile i needs group g. A group IS a centroid
    block, so the group-level filter maps 1:1 onto skipped blocks."""
    n, g = group_need.shape
    n_pad = (-n) % tile_n
    padded = jnp.pad(group_need, ((0, n_pad), (0, 0)))
    return jnp.any(padded.reshape(-1, tile_n, g), axis=1)


@functools.partial(jax.jit, static_argnames=("capacity",))
def compact_indices(mask: jnp.ndarray, *, capacity: int):
    """Stream compaction: indices of True entries, padded to ``capacity``.

    Returns (idx (capacity,) int32 — invalid slots point at 0 —,
    valid (capacity,) bool, count scalar). Deterministic order.
    """
    n = mask.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1            # slot per hit
    count = jnp.sum(mask.astype(jnp.int32))
    src = jnp.arange(n, dtype=jnp.int32)
    slot = jnp.where(mask, pos, capacity)                   # misses -> OOB
    idx = jnp.zeros((capacity,), jnp.int32).at[slot].set(src, mode="drop")
    valid = jnp.arange(capacity) < jnp.minimum(count, capacity)
    return idx, valid, count
