"""Lloyd and triangle-inequality-filtered K-means (the KPynq algorithm).

Two exact algorithms with identical fixed points:

* ``lloyd``      — the standard baseline the paper compares against
                   (N*K distance evaluations per iteration).
* ``yinyang``    — KPynq's multi-level filter. ``n_groups == 1`` is the
                   paper's *point-level* filter alone (Hamerly-style
                   global bound); ``n_groups > 1`` adds the
                   *group-level* filter (Yinyang-style per-group lower
                   bounds).

Both are pure JAX (`lax.while_loop`), run anywhere, and report a
``distance_evals`` counter — the paper's work-efficiency metric. The
actual FLOP saving on TPU is realised by the Pallas block-skip /
compaction kernels in ``repro.kernels``; this module is the algorithmic
ground truth they are tested against.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .distances import (pairwise_dists, pairwise_sq_dists, row_norms_sq,
                        rowwise_dists)


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------

def centroid_sums(points, assignments, k, weights=None):
    """Per-cluster partial sums + counts — the psum'able half of the
    centroid update (the distributed fit reduces these across shards
    before dividing).

    ``weights``: optional (N,) per-point sample weights — the sums
    become weighted sums and the counts the per-cluster weighted mass.
    ``None`` keeps the exact pre-weight program (and uniform weights
    of 1.0 are bit-identical to it: multiplying by 1.0f is exact)."""
    pts = points.astype(jnp.float32)
    if weights is None:
        sums = jax.ops.segment_sum(pts, assignments,
                                   num_segments=k)                 # (K, D)
        counts = jax.ops.segment_sum(
            jnp.ones((pts.shape[0],), jnp.float32), assignments,
            num_segments=k)                                        # (K,)
    else:
        w = weights.astype(jnp.float32)
        sums = jax.ops.segment_sum(w[:, None] * pts, assignments,
                                   num_segments=k)
        counts = jax.ops.segment_sum(w, assignments, num_segments=k)
    return sums, counts


def onehot_centroid_sums(points, assignments, k):
    """:func:`centroid_sums` (unweighted) as one matrix product,
    ``onehot(assignments)^T @ points``, for the MXU, where the
    scatter-adds of ``segment_sum`` go one point at a time.

    The one-hot is built inside the product's operand, so XLA fuses it:
    the points and labels are read once, and no (N, K) array is
    stored. At ``HIGHEST`` every product of a coordinate is exact (the
    0/1 side is exact in bf16, so every term the bf16 passes drop
    multiplies a zero) and every sum accumulates in float32. The counts
    are the one-hot's column sums, exact below 2^24 points."""
    onehot = assignments[:, None] == jnp.arange(k, dtype=assignments.dtype)
    sums = jnp.einsum("nk,nd->kd", onehot.astype(jnp.float32),
                      points.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    return sums, jnp.sum(onehot, axis=0, dtype=jnp.float32)


def centroids_from_sums(sums, counts, prev_centroids):
    """Divide reduced sums by counts. Empty clusters keep their previous
    centroid (standard practice; also what keeps the filtered and
    unfiltered paths bit-identical). THE single copy of that rule."""
    safe = jnp.maximum(counts, 1.0)[:, None]
    return jnp.where(counts[:, None] > 0, sums / safe, prev_centroids)


def update_centroids(points, assignments, k, prev_centroids,
                     weights=None):
    """Segment-sum centroid update — O(N*D), the right formulation for
    CPU/scatter hardware. (On the TPU, the batch fit's
    ``engine.move_and_bounds`` takes the sums from
    :func:`onehot_centroid_sums` instead, where ``engine.onehot_sums``
    picks it; same math.)
    """
    sums, counts = centroid_sums(points, assignments, k, weights=weights)
    return centroids_from_sums(sums, counts, prev_centroids), counts


@functools.partial(jax.jit, static_argnames=("n_groups", "n_iters"))
def group_centroids(centroids: jnp.ndarray, n_groups: int, n_iters: int = 5):
    """Partition centroids into groups by clustering the centroids
    themselves (the Yinyang construction). Deterministic: seeds with a
    strided subset. Returns int32 group ids of shape (K,).

    Jitted (it is called eagerly by every fit driver, and an un-jitted
    ``fori_loop`` costs ~100ms of per-op dispatch even for tiny K). Its
    device ops run under the ``kpynq/group`` scope."""
    k = centroids.shape[0]
    if n_groups >= k:
        return jnp.arange(k, dtype=jnp.int32) % n_groups
    stride = max(k // n_groups, 1)
    with jax.named_scope("kpynq/group"):
        seeds = centroids[::stride][:n_groups]

        def body(_, seeds):
            d = pairwise_dists(centroids, seeds)
            gid = jnp.argmin(d, axis=1)
            new_seeds, _ = update_centroids(centroids, gid, n_groups, seeds)
            return new_seeds

        seeds = jax.lax.fori_loop(0, n_iters, body, seeds)
        return jnp.argmin(pairwise_dists(centroids, seeds),
                          axis=1).astype(jnp.int32)


class EvalCount(NamedTuple):
    """Precision-safe distance-evaluation counter.

    A single fp32 accumulator silently drops increments once the running
    total passes 2^24 (adding ``n*k`` per iteration at paper scale blows
    through that in one or two iterations). JAX runs with x64 disabled,
    so int64/float64 are unavailable on-device; instead we carry a
    compensated (hi, lo) fp32 pair (Fast2Sum): every rounding error of
    ``hi`` is captured exactly in ``lo``, keeping integer counts exact to
    ~2^48. ``total()`` collapses to one fp32 scalar (single final
    rounding) so ``KMeansResult.distance_evals`` keeps its scalar API.
    """
    hi: jnp.ndarray               # running sum, fp32
    lo: jnp.ndarray               # compensation term, fp32

    @staticmethod
    def of(x) -> "EvalCount":
        return EvalCount(jnp.asarray(x, jnp.float32), jnp.float32(0))

    def add(self, x) -> "EvalCount":
        x = jnp.asarray(x, jnp.float32)
        s = self.hi + x
        # Neumaier branch: recover the exact rounding error of hi + x
        big = jnp.where(jnp.abs(self.hi) >= jnp.abs(x), self.hi, x)
        small = jnp.where(jnp.abs(self.hi) >= jnp.abs(x), x, self.hi)
        return EvalCount(s, self.lo + ((big - s) + small))

    def total(self) -> jnp.ndarray:
        return self.hi + self.lo


class KMeansResult(NamedTuple):
    centroids: jnp.ndarray        # (K, D) fp32
    assignments: jnp.ndarray      # (N,) int32
    n_iters: jnp.ndarray          # scalar int32
    distance_evals: jnp.ndarray   # scalar fp32 (EvalCount.total())
    inertia: jnp.ndarray          # sum of squared distances to assigned


def _inertia(points, centroids, assignments, weights=None):
    d = rowwise_dists(points, centroids[assignments])
    d2 = d * d
    if weights is not None:
        d2 = d2 * weights.astype(jnp.float32)
    return jnp.sum(d2)


# --------------------------------------------------------------------------
# Lloyd baseline
# --------------------------------------------------------------------------

def lloyd(points, init_centroids, max_iters: int = 100, tol: float = 1e-4,
          weights=None):
    """Standard K-means — the CPU baseline of the paper's Table.
    ``weights``: optional (N,) sample weights (weighted centroid means
    and inertia; the distance work per iteration is unchanged)."""
    k = init_centroids.shape[0]
    n = points.shape[0]

    def cond(state):
        i, _, _, shift, _ = state
        return jnp.logical_and(i < max_iters, shift > tol)

    def body(state):
        i, centroids, _, _, evals = state
        d = pairwise_dists(points, centroids)
        assign = jnp.argmin(d, axis=1).astype(jnp.int32)
        new_c, _ = update_centroids(points, assign, k, centroids,
                                    weights=weights)
        shift = jnp.max(jnp.linalg.norm(new_c - centroids, axis=-1))
        return i + 1, new_c, assign, shift, evals.add(jnp.float32(n) * k)

    init = (jnp.int32(0), init_centroids.astype(jnp.float32),
            jnp.zeros(n, jnp.int32), jnp.float32(jnp.inf), EvalCount.of(0))
    i, centroids, assign, _, evals = jax.lax.while_loop(cond, body, init)
    return KMeansResult(centroids, assign, i, evals.total(),
                        _inertia(points, centroids, assign, weights))


# --------------------------------------------------------------------------
# KPynq multi-level filtered K-means (Yinyang/Hamerly family)
# --------------------------------------------------------------------------

class FilterState(NamedTuple):
    iteration: jnp.ndarray    # int32
    centroids: jnp.ndarray    # (K, D)
    assignments: jnp.ndarray  # (N,)
    ub: jnp.ndarray           # (N,)   upper bound on d(x, a(x))
    lb: jnp.ndarray           # (N, G) lower bound on d(x, nearest in group)
    shift: jnp.ndarray        # max centroid drift last iter
    distance_evals: EvalCount


@functools.partial(jax.jit, static_argnums=(3,))
def _init_filter_state(points, centroids, groups, n_groups, x2=None,
                       c2=None):
    """Initial exact assignment + bounds. ``x2``/``c2``: optional cached
    squared norms (the engine computes ``||x||^2`` once per fit and
    threads it through; passing them here keeps that single copy).
    Reductions run on SQUARED distances; only the (N,) / (N, G)
    outputs are sqrt'ed (monotone => identical bounds, one fewer
    (N, K) sqrt pass)."""
    n, k = points.shape[0], centroids.shape[0]
    d2 = pairwise_sq_dists(points, centroids, x2, c2)           # (N, K)
    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)
    ub = jnp.sqrt(jnp.min(d2, axis=1))
    # lb[x, g] = min over centroids in g, excluding the assigned one.
    d2_excl = d2.at[jnp.arange(n), assign].set(jnp.inf)
    lb = jnp.sqrt(jax.ops.segment_min(d2_excl.T, groups,
                                      num_segments=n_groups).T)  # (N, G)
    return FilterState(jnp.int32(0), centroids.astype(jnp.float32), assign,
                       ub, lb, jnp.float32(jnp.inf),
                       EvalCount.of(jnp.float32(n) * k))


def _filtered_step(points, state: FilterState, groups, n_groups: int, k: int,
                   x2=None, weights=None):
    """One KPynq iteration: centroid move -> bound maintenance ->
    point-level filter -> group-level filter -> masked distance pass.

    ``x2``: cached ``||x||^2`` (``yinyang`` computes it once per fit);
    the new centroids' ``||c||^2`` is computed once here and shared by
    the own-distance refresh and the masked pass. Reductions run on
    SQUARED distances (monotone, so results are identical) and sqrt
    only the reduced outputs."""
    n = points.shape[0]
    rows = jnp.arange(n)

    # 1. move centroids from current assignments; measure drift
    new_c, _ = update_centroids(points, state.assignments, k,
                                state.centroids, weights=weights)
    c2 = row_norms_sq(new_c)                       # once per iteration
    drift = jnp.linalg.norm(new_c - state.centroids, axis=-1)          # (K,)
    group_drift = jax.ops.segment_max(drift, groups, num_segments=n_groups)
    shift = jnp.max(drift)

    # 2. bound maintenance (triangle inequality)
    ub = state.ub + drift[state.assignments]
    lb = jnp.maximum(state.lb - group_drift[None, :], 0.0)
    glb = jnp.min(lb, axis=1)                                          # (N,)

    # 3. POINT-LEVEL FILTER: ub < min_g lb[g]  =>  zero distance work
    maybe = ub > glb
    # tighten ub with one exact distance for surviving points
    if x2 is None:
        d_own = rowwise_dists(points, new_c[state.assignments])
    else:
        own = new_c[state.assignments]
        d_own = jnp.sqrt(jnp.maximum(
            x2 - 2.0 * jnp.sum(points.astype(jnp.float32) * own, axis=-1)
            + c2[state.assignments], 0.0))
    ub_t = jnp.where(maybe, d_own, ub)
    need = ub_t > glb
    evals = state.distance_evals.add(jnp.sum(maybe.astype(jnp.float32)))

    # 4. GROUP-LEVEL FILTER: only groups with lb[x,g] < ub survive
    group_need = need[:, None] & (lb < ub_t[:, None])                  # (N, G)
    cand = group_need[:, groups]                                       # (N, K)
    evals = evals.add(jnp.sum(cand.astype(jnp.float32)))

    # 5. masked distance pass (the Distance Calculator). Algorithmically
    #    only `cand` entries are needed; the Pallas kernel skips
    #    non-candidate blocks — here we mask for exact semantics.
    d2_all = pairwise_sq_dists(points, new_c, x2, c2)
    d2_cand = jnp.where(cand, d2_all, jnp.inf)
    best_other = jnp.argmin(d2_cand, axis=1).astype(jnp.int32)
    best_other_d = jnp.sqrt(jnp.min(d2_cand, axis=1))
    new_assign = jnp.where(best_other_d < ub_t, best_other, state.assignments)
    new_ub = jnp.minimum(ub_t, best_other_d)

    # 6. refresh lb for computed groups: min distance in group excluding
    #    the (new) assigned centroid; untouched groups keep decayed lb.
    d2_excl = d2_cand.at[rows, new_assign].set(jnp.inf)
    lb_comp = jnp.sqrt(jax.ops.segment_min(d2_excl.T, groups,
                                           num_segments=n_groups).T)   # (N, G)
    new_lb = jnp.where(group_need, lb_comp, lb)
    # Exactness fix (Yinyang): when x is reassigned away from its old
    # centroid b, b re-enters the "non-assigned" pool of its group, at
    # exact distance d(x, b) = ub_t. A skipped old group's decayed lb can
    # exceed that, so cap it. (For computed groups lb_comp already
    # accounts for b; min() is a no-op there.)
    changed = best_other_d < ub_t
    old_group = groups[state.assignments]
    new_lb = new_lb.at[rows, old_group].min(jnp.where(changed, ub_t, jnp.inf))

    return FilterState(state.iteration + 1, new_c, new_assign, new_ub,
                       new_lb, shift, evals)


def yinyang(points, init_centroids, n_groups: int | None = None,
            max_iters: int = 100, tol: float = 1e-4, weights=None):
    """KPynq filtered K-means. ``n_groups=1`` -> point-level filter only;
    default ``K // 10`` groups (the Yinyang heuristic). ``weights``:
    optional (N,) sample weights — they enter the centroid means and
    the inertia only; the filters stay weight-independent."""
    k = init_centroids.shape[0]
    if n_groups is None:
        n_groups = max(k // 10, 1)
    n_groups = int(min(n_groups, k))
    groups = group_centroids(init_centroids.astype(jnp.float32), n_groups)
    x2 = row_norms_sq(points)                    # ONCE per fit
    state0 = _init_filter_state(points, init_centroids.astype(jnp.float32),
                                groups, n_groups, x2=x2)

    def cond(state):
        return jnp.logical_and(state.iteration < max_iters, state.shift > tol)

    def body(state):
        return _filtered_step(points, state, groups, n_groups, k, x2=x2,
                              weights=weights)

    state = jax.lax.while_loop(cond, body, state0)
    return KMeansResult(state.centroids, state.assignments, state.iteration,
                        state.distance_evals.total(),
                        _inertia(points, state.centroids, state.assignments,
                                 weights))
