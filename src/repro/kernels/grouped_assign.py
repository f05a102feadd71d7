"""Pallas TPU kernel: group-granular block-skip nearest-centroid search.

A kernel that skips (tile_n x tile_k) centroid blocks yields only the
global (min, argmin) — enough for Hamerly, not for Yinyang, whose
lower-bound refresh needs *per-group* minima. This kernel makes the
centroid block THE GROUP: a (point tile, group) block
is exactly one group-level filter decision, and a skipped block is
skipped MXU work.

Per live block it maintains:

* the running global ``(min_sq_dist, argmin)`` across groups, visited
  in ascending group order so a strict ``<`` keeps the first match, and
* per-(point, group) ``(min, argmin, second_min)`` — precisely the
  triple the engine needs to rebuild the Yinyang lower bound
  ``min_{c in g, c != assigned} d(x, c)`` without materialising any
  (N, K) distance matrix: the excluded centroid can only collide with
  the group argmin, in which case the second-min is the answer.

Centroids arrive pre-bucketed as ``c_grouped`` (G, Lmax, D) with a
parallel ``ids`` (G, Lmax) int32 table (-1 padding); padded slots are
masked to +inf inside the kernel so empty/ragged groups are exact.

Grid. ``(N_pad / tile_n, ceil(G / gs))``: one step per point tile and
block of ``gs`` groups, and the groups are a loop inside the step. The
wrapper turns the block mask into, per step, the list of its live
groups in ascending order and their count; the loop visits just those,
so a dead (tile, group) block costs neither a grid step nor a DMA nor a
loop iteration. ``gs`` follows from the shapes (:func:`groups_per_step`):
all ``G`` where their blocks fit ``GROUP_VMEM_BUDGET``, so the centroid
blocks' index maps are constant and they are fetched once per call;
else the largest multiple of 8 that fits (K=16,384, D=128, Lmax 72:
184 of 1,638 groups, 9 steps a tile).

Layout (what the TPU compiler accepts: a block's last two dims are
multiples of (8, 128) or the full array dims). The kernel works on the
TRANSPOSED distance tile ``(Lmax, tile_n)``, so every per-point value
is a lane-dense ``(1, tile_n)`` row: points' norms and the global
outputs are ``(1, N_pad)`` rows, the per-group outputs are
``(G, N_pad)`` in ``(gs, tile_n)`` blocks whose row ``g`` a live group
writes, and the per-group centroid norms / ids are ``(G, Lmax, 1)``
columns. A column pads each group's ``Lmax`` values to 128 lanes; at
the IVF1024 shape (G=102, Lmax 72) the centroids and the two columns
take 22 MiB double-buffered, over the 16 MiB default scoped VMEM. The
kernel raises its limit to ``VMEM_LIMIT`` (v5e has 128 MiB) rather than
single-buffer the blocks or re-lay the columns: one rule then serves
``gs == G``, where the blocks never change, and ``gs < G``, where the
second buffer fetches the next group block behind the current one, and
the columns broadcast along the lanes with no relayout in the loop.

The live lists are scalar-prefetch operands in SMEM: 4 bytes per
(tile, group) block plus 4 per step, of the chip's 1 MiB, so
``N / tile_n * G`` must stay under about 256K (``uci-xlarge``: 102,400;
IVF1024 at 262,144 points: 104,448); past that the compiler refuses
the kernel. On the chip ``tile_n`` must be a multiple of 128 (or cover
all of N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# v5e's scoped VMEM: 16 MiB by default, 128 MiB physical per core. The
# kernel asks for VMEM_LIMIT, of which GROUP_VMEM_BUDGET may go to the
# blocks whose size follows the groups a step handles; the rest is for
# the point tile and the per-group temporaries.
VMEM_LIMIT = 64 << 20
GROUP_VMEM_BUDGET = 40 << 20


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _group_block_bytes(gs: int, lmax: int, d: int, tile_n: int) -> int:
    """VMEM of the blocks that scale with ``gs``: the centroids
    ``(gs, Lmax, D)``, the ``(gs, Lmax, 1)`` norm and id columns, each
    lane-padded to 128, and the three ``(gs, tile_n)`` per-group outputs;
    all double-buffered."""
    lm = _round_up(lmax, 8)
    cols = gs * lm * (_round_up(d, 128) + 2 * 128)
    rows = 3 * _round_up(gs, 8) * _round_up(tile_n, 128)
    return 2 * 4 * (cols + rows)


def groups_per_step(n_groups: int, lmax: int, d: int, tile_n: int) -> int:
    """Groups one grid step handles: all ``G`` where their blocks fit
    ``GROUP_VMEM_BUDGET``, else the largest multiple of 8 that fits (a
    block's second-minor dim must be a multiple of 8 or the full dim),
    and never fewer than 8."""
    if _group_block_bytes(n_groups, lmax, d, tile_n) <= GROUP_VMEM_BUDGET:
        return n_groups
    # the bytes are linear in gs over multiples of 8
    eights = GROUP_VMEM_BUDGET // _group_block_bytes(8, lmax, d, tile_n)
    return min(n_groups, 8 * max(1, eights))


def _grouped_assign_kernel(live_ref, count_ref, x_ref, x2_ref, c_ref,
                           c2_ref, ids_ref, best_ref, idx_ref, gmin_ref,
                           garg_ref, gmin2_ref, *, gs: int, lmax: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    step = i * pl.num_programs(1) + j

    @pl.when(j == 0)
    def _init_global():
        # the global running (min, argmin) stays resident over j
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    # per-group outputs default to "skipped"; live rows overwrite them
    gmin_ref[...] = jnp.full_like(gmin_ref, jnp.inf)
    garg_ref[...] = jnp.full_like(garg_ref, -1)
    gmin2_ref[...] = jnp.full_like(gmin2_ref, jnp.inf)

    def group(t, carry):
        gl = live_ref[step * gs + t]            # the step's t-th live group
        x = x_ref[...].astype(jnp.float32)                  # (tn, D)
        c = c_ref[gl].astype(jnp.float32)                   # (Lmax, D)
        ids = ids_ref[gl]                                   # (Lmax, 1)
        # squared norms arrive precomputed (once per fit for x2, once
        # per iteration for c2) — the kernel only does the cross term
        x2 = x2_ref[...]                                    # (1, tn)
        c2 = c2_ref[gl]                                     # (Lmax, 1)
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

        row = pl.ds(gl, 1)
        gmin_ref[row, :] = min1
        garg_ref[row, :] = arg
        gmin2_ref[row, :] = min2

        better = min1 < best_ref[...]
        idx_ref[...] = jnp.where(better, arg, idx_ref[...])
        best_ref[...] = jnp.minimum(best_ref[...], min1)
        return carry

    jax.lax.fori_loop(0, count_ref[step], group, 0)


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
    gs = groups_per_step(g, lmax, d, tile_n)
    gb = -(-g // gs)
    n_pad = (-n) % tile_n
    xp = jnp.pad(x, ((0, n_pad), (0, 0)))
    np_ = xp.shape[0]
    gn = np_ // tile_n
    # per step (tile, group block): its live groups' slots in ascending
    # order, then the dead ones (never read), and how many are live; the
    # last block's slots past G are dead
    flags = jnp.pad(block_mask.astype(bool),
                    ((0, 0), (0, gb * gs - g))).reshape(gn, gb, gs)
    slot = jnp.arange(gs, dtype=jnp.int32)
    live = jnp.sort(jnp.where(flags, slot, gs + slot), axis=-1).reshape(-1)
    count = jnp.sum(flags, axis=-1, dtype=jnp.int32).reshape(-1)
    if x2 is None:
        x2 = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
    x2p = jnp.pad(x2.astype(jnp.float32), (0, n_pad))[None, :]  # (1, Np)
    if c2g is None:
        c2g = jnp.sum(c_grouped.astype(jnp.float32) ** 2, axis=-1)
    c2g = c2g.astype(jnp.float32)[:, :, None]                   # (G, Lmax, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(gn, gb),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i, j, *_: (i, 0)),       # x
            pl.BlockSpec((1, tile_n), lambda i, j, *_: (0, i)),       # x2
            pl.BlockSpec((gs, lmax, d), lambda i, j, *_: (j, 0, 0)),  # c
            pl.BlockSpec((gs, lmax, 1), lambda i, j, *_: (j, 0, 0)),  # c2
            pl.BlockSpec((gs, lmax, 1), lambda i, j, *_: (j, 0, 0)),  # ids
        ],
        out_specs=[
            pl.BlockSpec((1, tile_n), lambda i, j, *_: (0, i)),       # best
            pl.BlockSpec((1, tile_n), lambda i, j, *_: (0, i)),       # idx
            pl.BlockSpec((gs, tile_n), lambda i, j, *_: (j, i)),      # gmin
            pl.BlockSpec((gs, tile_n), lambda i, j, *_: (j, i)),      # garg
            pl.BlockSpec((gs, tile_n), lambda i, j, *_: (j, i)),      # gmin2
        ],
    )
    best, idx, gmin, garg, gmin2 = pl.pallas_call(
        functools.partial(_grouped_assign_kernel, gs=gs, lmax=lmax),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, np_), jnp.int32),
            jax.ShapeDtypeStruct((g, np_), jnp.float32),
            jax.ShapeDtypeStruct((g, np_), jnp.int32),
            jax.ShapeDtypeStruct((g, np_), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="grouped_assign",        # the kernel's name in a profile
    )(live, count, xp, x2p, c_grouped.astype(jnp.float32), c2g,
      ids.astype(jnp.int32)[:, :, None])
    return (best[0, :n], idx[0, :n], gmin[:, :n].T, garg[:, :n].T,
            gmin2[:, :n].T)
