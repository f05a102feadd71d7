"""Pallas TPU kernel: group-granular block-skip nearest-centroid search.

The original ``filtered_assign`` kernel skips (tile_n x tile_k) blocks
but only yields the global (min, argmin) — enough for Hamerly, not for
Yinyang, whose lower-bound refresh needs *per-group* minima. This
kernel makes the centroid grid dimension THE GROUP: the grid is
``(N/tile_n, G)``, each step loads one group's (Lmax-padded) centroid
bucket, and a skipped block is exactly one group-level filter decision
realised as skipped MXU work.

Per live block it maintains:

* the running global ``(min_sq_dist, argmin)`` across groups
  (sequential revisits over the minor grid axis, as in
  ``filtered_assign``), and
* per-(point, group) ``(min, argmin, second_min)`` — precisely the
  triple the engine needs to rebuild the Yinyang lower bound
  ``min_{c in g, c != assigned} d(x, c)`` without materialising any
  (N, K) distance matrix: the excluded centroid can only collide with
  the group argmin, in which case the second-min is the answer.

Centroids arrive pre-bucketed as ``c_grouped`` (G, Lmax, D) with a
parallel ``ids`` (G, Lmax) int32 table (-1 padding); padded slots are
masked to +inf inside the kernel so empty/ragged groups are exact.

Layout (what the TPU compiler accepts: a block's last two dims are
multiples of (8, 128) or the full array dims). The kernel works on the
TRANSPOSED distance tile ``(Lmax, tile_n)``, so every per-point value
is a lane-dense ``(1, tile_n)`` row: points' norms and the global
outputs are ``(1, N_pad)`` rows, the per-group outputs are
``(G, N_pad)`` whose ``(G, tile_n)`` block stays resident across the
group axis (row ``g`` is written at step ``g``), and the per-group
centroid norms / ids are ``(G, Lmax, 1)`` columns. The block mask is a
scalar-prefetch operand in SMEM, read by ``pl.when``: 4 bytes per
(tile, group) block of the chip's 1 MiB SMEM, so ``N / tile_n * G``
must stay under about 256K (``uci-xlarge``: 102,400); past that the
compiler refuses the kernel. On the chip ``tile_n`` must be a multiple
of 128 (or cover all of N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _grouped_assign_kernel(mask_ref, x_ref, x2_ref, c_ref, c2_ref, ids_ref,
                           best_ref, idx_ref, gmin_ref, garg_ref, gmin2_ref,
                           *, n_groups: int, lmax: int):
    i = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        # global running (min, argmin), and per-group outputs defaulting
        # to "skipped" — the (G, tile_n) blocks stay resident over g
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        gmin_ref[...] = jnp.full_like(gmin_ref, jnp.inf)
        garg_ref[...] = jnp.full_like(garg_ref, -1)
        gmin2_ref[...] = jnp.full_like(gmin2_ref, jnp.inf)

    @pl.when(mask_ref[i * n_groups + g] != 0)
    def _compute():
        x = x_ref[...].astype(jnp.float32)                  # (tn, D)
        c = c_ref[0].astype(jnp.float32)                    # (Lmax, D)
        ids = ids_ref[0]                                    # (Lmax, 1)
        # squared norms arrive precomputed (once per fit for x2, once
        # per iteration for c2) — the kernel only does the cross term
        x2 = x2_ref[...]                                    # (1, tn)
        c2 = c2_ref[0]                                      # (Lmax, 1)
        # full f32 (core.distances.CROSS_PRECISION): the chip's default
        # single bf16 pass errs by about a near-tie's distance gap
        cross = jax.lax.dot_general(
            c, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)             # (Lmax, tn)
        d2 = jnp.maximum(x2 - 2.0 * cross + c2, 0.0)
        d2 = jnp.where(ids >= 0, d2, jnp.inf)

        # first-match argmin as two keepdims min passes (no argmin or
        # rank-1 values inside the kernel)
        slot = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
        min1 = jnp.min(d2, axis=0, keepdims=True)           # (1, tn)
        arg_local = jnp.min(jnp.where(d2 <= min1, slot, lmax), axis=0,
                            keepdims=True)
        onehot = slot == arg_local                          # (Lmax, tn)
        arg = jnp.sum(jnp.where(onehot, ids, 0), axis=0, keepdims=True)
        min2 = jnp.min(jnp.where(onehot, jnp.inf, d2), axis=0,
                       keepdims=True)

        row = jax.lax.broadcasted_iota(jnp.int32, gmin_ref.shape, 0) == g
        gmin_ref[...] = jnp.where(row, min1, gmin_ref[...])
        garg_ref[...] = jnp.where(row, arg, garg_ref[...])
        gmin2_ref[...] = jnp.where(row, min2, gmin2_ref[...])

        better = min1 < best_ref[...]
        idx_ref[...] = jnp.where(better, arg, idx_ref[...])
        best_ref[...] = jnp.minimum(best_ref[...], min1)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def grouped_assign(x: jnp.ndarray, c_grouped: jnp.ndarray,
                   ids: jnp.ndarray, block_mask: jnp.ndarray, *,
                   tile_n: int = 256, interpret: bool = False,
                   x2: jnp.ndarray | None = None,
                   c2g: jnp.ndarray | None = None):
    """Group-block-skipping nearest-centroid search with per-group stats.

    x: (N, D); c_grouped: (G, Lmax, D) group-bucketed centroids;
    ids: (G, Lmax) int32 original centroid index per slot (-1 = pad);
    block_mask: (ceil(N/tile_n), G) bool/int — True where the group
    must be scored for that point tile. ``x2`` (N,) / ``c2g``
    (G, Lmax): optional precomputed squared norms — the engine caches
    ``||x||^2`` once per fit and ``||c||^2`` once per iteration and
    passes them here so the kernel never recomputes them (``None``
    computes locally; identical results).

    Returns ``(best (N,) fp32 sq-dist, idx (N,) int32,
    gmin (N, G) fp32, garg (N, G) int32, gmin2 (N, G) fp32)``; skipped
    (tile, group) blocks read as (inf, -1, inf), fully-skipped rows as
    (inf, -1) globally.
    """
    n, d = x.shape
    g, lmax = ids.shape
    n_pad = (-n) % tile_n
    xp = jnp.pad(x, ((0, n_pad), (0, 0)))
    np_ = xp.shape[0]
    gn = np_ // tile_n
    mask = block_mask.astype(jnp.int32).reshape(gn * g)
    if x2 is None:
        x2 = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
    x2p = jnp.pad(x2.astype(jnp.float32), (0, n_pad))[None, :]  # (1, Np)
    if c2g is None:
        c2g = jnp.sum(c_grouped.astype(jnp.float32) ** 2, axis=-1)
    c2g = c2g.astype(jnp.float32)[:, :, None]                   # (G, Lmax, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gn, g),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i, j, m: (i, 0)),      # x
            pl.BlockSpec((1, tile_n), lambda i, j, m: (0, i)),      # x2
            pl.BlockSpec((1, lmax, d), lambda i, j, m: (j, 0, 0)),  # c
            pl.BlockSpec((1, lmax, 1), lambda i, j, m: (j, 0, 0)),  # c2
            pl.BlockSpec((1, lmax, 1), lambda i, j, m: (j, 0, 0)),  # ids
        ],
        out_specs=[
            pl.BlockSpec((1, tile_n), lambda i, j, m: (0, i)),      # best
            pl.BlockSpec((1, tile_n), lambda i, j, m: (0, i)),      # idx
            pl.BlockSpec((g, tile_n), lambda i, j, m: (0, i)),      # gmin
            pl.BlockSpec((g, tile_n), lambda i, j, m: (0, i)),      # garg
            pl.BlockSpec((g, tile_n), lambda i, j, m: (0, i)),      # gmin2
        ],
    )
    best, idx, gmin, garg, gmin2 = pl.pallas_call(
        functools.partial(_grouped_assign_kernel, n_groups=g, lmax=lmax),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, np_), jnp.int32),
            jax.ShapeDtypeStruct((g, np_), jnp.float32),
            jax.ShapeDtypeStruct((g, np_), jnp.int32),
            jax.ShapeDtypeStruct((g, np_), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="grouped_assign",        # the kernel's name in a profile
    )(mask, xp, x2p, c_grouped.astype(jnp.float32), c2g,
      ids.astype(jnp.int32)[:, :, None])
    return (best[0, :n], idx[0, :n], gmin[:, :n].T, garg[:, :n].T,
            gmin2[:, :n].T)
