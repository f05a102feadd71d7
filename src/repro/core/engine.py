"""Device-resident filtered K-means execution engine.

This is the single executor behind the KPynq filter family — ONE pass
core, three drivers. The layering (see ``docs/architecture.md``):

* :class:`PassCore` — the candidate-pass dispatch (oracle / compact /
  ladder / pallas) plus the :func:`move_and_bounds` epilogue, the only
  copy of the filtered iteration (``_loop_body`` is the only
  candidate-pass loop body in the repo);
* :class:`Reducer` — the collective axis: identity locally,
  psum/pmax over mesh axes inside ``shard_map`` (with optional int8
  compression of the (K, D) partial-sums payload only);
* the centroid-update strategies — :data:`CONVERGENCE_UPDATE` (batch
  mean + tol-on-drift convergence) vs :data:`EMA_UPDATE` (the
  streaming decayed count-weighted EMA);
* ``sample_weight`` threads through :func:`centroid_sums`, the
  inertia, and the EMA's effective counts in this one place, so every
  backend x every driver is weighted by the same implementation
  (weights never touch bounds or filters — work saving is unchanged,
  and ``None``/uniform-1.0 weights are bit-identical).

The three drivers are thin instantiations: :func:`fit` (this module) =
PassCore + local reducer + convergence, host-picked capacity buckets;
``repro.core.distributed.distributed_yinyang`` = the same
:func:`fit_core` inside ``shard_map`` + psum reducer + the in-trace
capacity ladder; ``repro.streaming.StreamingKMeans`` =
:func:`stream_step` = one PassCore pass + (local|psum) reducer + EMA.

The iteration loop realises BOTH filter levels as skipped work:

* the whole fit runs under ``lax.while_loop`` — zero host round-trips
  per iteration. The only host syncs are capacity-bucket transitions
  (O(log N) of them, counted in :class:`EngineStats`), not one per
  iteration;
* **point-level compaction**: surviving points are stream-compacted
  into a padded buffer whose capacity comes from a fixed power-of-two
  lattice, so XLA compiles a small, bounded set of programs;
* **centroid-level compaction**: each candidate's *surviving groups*
  are compacted into a padded per-point group bucket and only those
  groups' centroids are gathered for the distance pass — the
  group-level filter becomes skipped FLOPs, not just bookkeeping;
* **norm caching**: ``||x||^2`` is computed ONCE PER FIT and carried
  through the ``lax.while_loop`` (``EngineCarry.x2``); ``||c||^2`` is
  computed once per iteration by :func:`move_and_bounds` and shared by
  the own-distance refresh and the next candidate pass
  (``EngineCarry.c2``). On the compact backend the own-distance
  refresh itself runs on the COMPACTED survivor buffer instead of all
  N rows (``refresh_ub=True`` in :func:`compact_candidate_pass`);
* the Pallas block-skip kernel (``repro.kernels.grouped_assign``) slots
  in as the TPU backend behind the same interface;
* the bucket machinery also exists fully IN-TRACE for hostless loops
  (:func:`cap_ladders` / :func:`select_bucket` /
  :func:`ladder_candidate_pass`): a static capacity lattice switched
  per iteration with ``lax.switch`` — what ``repro.core.distributed``
  runs inside its ``shard_map`` body, where a host sync is not an
  option.

Backend selection (``backend=`` on :func:`fit`):

``"oracle"``
    Masked-dense pass over all N points every iteration — computes every
    distance and discards the filtered ones. Ground truth / debugging.
``"compact"``
    The two-level compaction path above. Default off-TPU: on CPU/GPU
    this is what turns filter rates into wall-clock speedup.
``"pallas"``
    Group-granular block-skip Pallas kernel (compiled on TPU,
    interpreted on CPU). Default on TPU, where per-point gathers are
    hostile but skipping whole (tile_n x group) blocks is free.
``"lloyd"``
    The jit-cached reference Lloyd loop — one dense GEMM per
    iteration, no filter bookkeeping. The right call below the
    work crossover (see ``EngineConfig.lloyd_max_work``) and a
    legitimate autotuner outcome for filter-hostile shapes.
``"auto"``
    Consults the tuned configuration (see below) when one exists;
    otherwise ``"lloyd"`` for tiny problems (``n * k <=
    lloyd_max_work``), ``"pallas"`` on TPU, ``"compact"`` elsewhere.

Autotuning (``tune=`` on :func:`fit`): every fixed knob of this engine
— ``tile_n``, ``min_cap``, ``chunk``, the group-gather crossover, the
downshift hysteresis, the backend itself — is a measured choice, and
the right value depends on (platform, N, K, D). ``tune="auto"``
(default) consults the persistent tuning cache
(:mod:`repro.tune`, ``~/.cache/repro_kmeans_tune.json`` unless
``REPRO_KMEANS_TUNE_CACHE`` overrides) and uses the cached winner for
this problem signature; ``tune="force"`` runs the measured search on a
cache miss and persists the winner; ``tune="off"`` uses the built-in
defaults. Tuned configurations change SHAPES AND DISPATCH ONLY — the
fixed point (assignments, inertia) is bit-identical for every
configuration (``tests/test_tune.py`` asserts this).

Every backend is exact: fixed points are identical to Lloyd's
(``tests/test_engine.py`` checks assignments/inertia parity across the
whole matrix). The split-loop construction (candidate pass for
iteration *i* runs at the top of body *i+1*, with a single epilogue
pass after the loop) is what lets the bucket conditions live in the
``while_loop`` *cond* without ever re-doing or skipping work.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from ..obs import ring as _obs_ring
from ..obs.metrics import normalize_obs
from ..obs.ring import N_COUNTERS, RING_COLUMNS
from ..obs.trace import compile_count, span
from ..platform import pallas_interpret
from .distances import (CROSS_PRECISION, pairwise_dists,
                        pairwise_sq_dists, row_norms_sq, rowwise_dists)
from .kmeans import (EvalCount, KMeansResult, _init_filter_state,
                     centroid_sums, centroids_from_sums, group_centroids,
                     lloyd, onehot_centroid_sums)

BACKENDS = ("oracle", "compact", "pallas")

# Default backend="auto" work crossover: problems with n*k at or below
# this route straight to the reference Lloyd loop. The reason is a CPU
# measurement at uci-small scale, where one dense (N, K) GEMM per
# iteration beat the filtered engine's bound bookkeeping; no chip run
# has measured the crossover. The fixed point is identical
# (tests/test_engine.py parity matrix), only distance_evals differ. The
# per-signature tuned value lives in EngineConfig.lloyd_max_work.
AUTO_LLOYD_MAX_WORK = 1 << 17

# jit-cached Lloyd for the tiny-problem route: calling the bare
# function would re-trace its while_loop on every fit, costing more
# than the fit itself at these sizes
_lloyd_jit = functools.partial(jax.jit, static_argnames=(
    "max_iters", "tol"))(lambda points, init_c, weights, *, max_iters,
                         tol: lloyd(points, init_c, max_iters, tol,
                                    weights=weights))


# --------------------------------------------------------------------------
# engine configuration (the autotuner's search space)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """One point in the engine's configuration space.

    Every field is a measured choice the autotuner (:mod:`repro.tune`)
    searches per (platform, N, K, D) signature; none of them affects
    the fixed point — only shapes, dispatch, and wall-clock.

    backend : "auto" | "oracle" | "compact" | "pallas" | "lloyd"
        Candidate-pass realisation. "auto" defers to the platform /
        ``lloyd_max_work`` rules in :func:`fit`.
    tile_n : point-tile height of the Pallas block-skip kernels.
    min_cap : floor of the power-of-two point-capacity lattice.
    chunk : largest compacted candidate count for which the per-point
        group-gather path is considered (above it the dense GEMM on
        the survivor buffer wins; XLA gathers scale worse than BLAS).
    group_gather_factor : the group-gather path is taken only when
        ``cap_g * l_max * group_gather_factor <= k`` — i.e. the group
        filter must remove at least this multiple of K before
        per-point gathers beat one dense (cap_n, K) matmul.
    down_n / down_g : downshift hysteresis. A running segment exits to
        a smaller bucket when ``n_cand * down_n <= cap_n`` (resp.
        ``gmax * down_g <= cap_g``); 0 disables that downshift axis.
    refresh_in_pass : where the own-distance refresh of *maybe*
        survivors runs on the compact backend. True = on the compacted
        survivor buffer inside the candidate pass (no full-N rowwise
        work, but capacity buckets are sized by the larger maybe-count);
        False = as a full-N masked rowwise pass in
        :func:`move_and_bounds` (costs one gather+dot over N per
        iteration, but the refresh prunes the candidate set BEFORE
        compaction, so buckets track the smaller need-count). Which
        side wins is a measured shape property — gather-hostile wide-D
        problems favour True, GEMM-strong small-D CPU shapes False.
    lloyd_max_work : backend="auto" routes ``n * k <= lloyd_max_work``
        straight to the dense Lloyd loop.
    """
    backend: str = "auto"
    tile_n: int = 256
    min_cap: int = 256
    chunk: int = 2048
    group_gather_factor: int = 4
    down_n: int = 2
    down_g: int = 4
    refresh_in_pass: bool = False
    lloyd_max_work: int = AUTO_LLOYD_MAX_WORK

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "EngineConfig":
        """Tolerant inverse of :meth:`to_dict` (unknown keys from a
        newer/older cache version are dropped, missing keys default)."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


DEFAULT_CONFIG = EngineConfig()


def use_groups_decision(*, cap_n: int, cap_g: int, l_max: int, k: int,
                        chunk: int, group_gather_factor: int) -> bool:
    """The compact pass's group-gather vs dense-GEMM crossover — THE
    single copy of the rule, shared by the pass (trace-time), the
    driver (per-segment stats), and the tuner (search space)."""
    return (cap_g * l_max * group_gather_factor <= k) and cap_n <= chunk


# The one-hot product's MXU work per point grows with K*D; the
# scatter-adds' cost ~14-17 ns a point whatever K. Timed on one TPU v5
# lite, scatter-adds against the product at HIGHEST (ms a call): N =
# 2,458,285, D = 68, K = 50: 41.2 against 1.32; N = 262,144, D = 128,
# K = 256: 4.10 / 0.68, K = 1,024: 4.11 / 1.48, K = 2,048: 3.56 / 2.83,
# K = 4,096: 3.68 / 5.50, K = 16,384: 3.70 / 23.8. The product takes
# every K*D up to 2,048 x 128. (Three bf16 parts of the points in three
# bf16 products, exact too, were slower at every shape: 3.35 ms at
# census, 1.82 at K = 1,024.)
ONEHOT_SUMS_MAX_KD = 2_048 * 128


def onehot_sums(k: int, d: int, *, platform: str,
                weighted: bool = False) -> bool:
    """Whether :func:`move_and_bounds` computes the centroid sums as the
    one-hot MXU product (:func:`~repro.core.kmeans.onehot_centroid_sums`)
    rather than the scatter-adds of
    :func:`~repro.core.kmeans.centroid_sums`: on the TPU, unweighted,
    and at a per-point product work ``k * d`` the MXU wins at."""
    return platform == "tpu" and not weighted and \
        k * d <= ONEHOT_SUMS_MAX_KD


# --------------------------------------------------------------------------
# the pass core's two strategy axes: Reducer (which collective) and
# the centroid-update rule (which epilogue)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Reducer:
    """Collective parameterisation of the pass core.

    The ONLY thing that differs between the single-device fit and the
    ``shard_map`` fit is which reduction joins the per-shard centroid
    partial sums (and the scalar telemetry): identity locally,
    ``lax.psum``/``pmax`` over the mesh axes in the distributed
    drivers. Frozen + hashable so a Reducer can ride in a jit-static
    :class:`PassCore`.

    ``compress=True`` int8-compresses the (K, D) partial-sums payload
    ONLY (:meth:`sums`); counts, weights and scalars always reduce
    exactly (:meth:`add` / :meth:`max`).
    """
    axes: tuple = ()               # () = local (identity reductions)
    compress: bool = False

    @property
    def is_local(self) -> bool:
        return not self.axes

    def sums(self, x):
        """Reduce the (K, D) centroid partial sums — the one payload
        eligible for int8 compression (error-feedback-free single-shot
        absmax scaling; relative error ~1/127, self-correcting across
        iterations)."""
        if not self.axes:
            return x
        if not self.compress:
            return jax.lax.psum(x, self.axes)
        scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return jax.lax.psum(q.astype(jnp.float32) * scale, self.axes)

    def add(self, x):
        """Exact sum reduction (counts, eval counters, inertia)."""
        return x if not self.axes else jax.lax.psum(x, self.axes)

    def max(self, x):
        """Max reduction (candidate counts, group high-waters)."""
        return x if not self.axes else jax.lax.pmax(x, self.axes)


LOCAL_REDUCER = Reducer()


@dataclasses.dataclass(frozen=True)
class ConvergenceUpdate:
    """Batch-fit centroid rule: mean of the reduced weighted sums,
    empty clusters keep their previous centroid. Paired with the
    tol-on-drift convergence test of the fit loops. ``clamp_gdrift``
    stays False: an empty Yinyang group's ``segment_max`` drift is
    ``-inf``, which the batch bound decay deliberately turns into a
    vacuous (+inf) lower bound."""
    clamp_gdrift: bool = False

    def apply(self, sums, counts, centroids, carry_counts, decay):
        return centroids_from_sums(sums, counts, centroids), counts


@dataclasses.dataclass(frozen=True)
class EMAUpdate:
    """Streaming centroid rule: the decayed count-weighted EMA
    ``c <- (decay * n_c * c + sum_batch) / (decay * n_c + b_c)`` —
    ``decay=1`` is pure count-weighting (per-centroid 1/n learning
    rate), ``decay<1`` caps the memory at ~1/(1-decay) batches. THE
    single copy of the update rule, shared by the local and sharded
    streaming steps. ``clamp_gdrift=True``: an empty group's -inf
    drift would otherwise poison the caller's cumulative drift ledger
    (inf - inf = NaN on the next inflation)."""
    clamp_gdrift: bool = True

    def apply(self, sums, counts, centroids, carry_counts, decay):
        dec = carry_counts * decay
        new_counts = dec + counts
        tot = dec[:, None] * centroids + sums
        # fractional decayed counts: guard with an epsilon, not the
        # batch fit's max(counts, 1) (which assumes integer counts)
        new_c = jnp.where(new_counts[:, None] > 1e-6,
                          tot / jnp.maximum(new_counts, 1e-6)[:, None],
                          centroids)
        return new_c, new_counts


CONVERGENCE_UPDATE = ConvergenceUpdate()
EMA_UPDATE = EMAUpdate()


class MoveOut(NamedTuple):
    """Everything :func:`move_and_bounds` produces. Batch drivers read
    ``centroids``/``c2``/``ub``/``lb``/``need``/``shift``/``tightened``;
    the streaming step additionally reads ``counts`` (the carried
    effective counts after the EMA), ``drift``/``gdrift`` (fed to the
    host drift ledger) and ``batch_counts`` (this batch's per-centroid
    weighted mass, pre-EMA)."""
    centroids: jnp.ndarray     # (K, D) after the update rule
    c2: jnp.ndarray            # (K,) ||centroids||^2, once per iteration
    counts: jnp.ndarray        # (K,) rule-dependent carried counts
    ub: jnp.ndarray            # (N,) drift-inflated (maybe refreshed)
    lb: jnp.ndarray            # (N, G) drift-decayed
    need: jnp.ndarray          # (N,) pending candidate mask
    shift: jnp.ndarray         # f32 max centroid drift
    tightened: jnp.ndarray     # f32 own-distance refreshes implied
    drift: jnp.ndarray         # (K,) per-centroid drift this move
    gdrift: jnp.ndarray        # (G,) per-group max drift this move
    batch_counts: jnp.ndarray  # (K,) this pass's weighted mass


# --------------------------------------------------------------------------
# shared per-iteration pieces (every driver's PassCore runs these)
# --------------------------------------------------------------------------

def move_and_bounds(points, centroids, assignments, ub, lb, groups,
                    *, k: int, n_groups: int,
                    reducer: Reducer = LOCAL_REDUCER,
                    update=CONVERGENCE_UPDATE, counts=None, decay=None,
                    weights=None, x2=None, refresh: bool = True,
                    onehot: bool = False):
    """Centroid move + triangle-inequality bound maintenance + the
    point-level filter — the pass core's move half, shared VERBATIM by
    every driver (batch, sharded, streaming).

    ``reducer``: which collective joins the per-shard centroid partial
    sums (identity locally, psum over the mesh axes in the distributed
    drivers — int8 compression applies to the (K, D) sums only).

    ``update``: the centroid rule — :data:`CONVERGENCE_UPDATE` (batch
    mean, tol-convergence drivers) or :data:`EMA_UPDATE` (decayed
    count-weighted streaming EMA; needs ``counts``/``decay``).

    ``weights``: optional (N,) per-point sample weights. They enter the
    partial sums and counts ONLY — bounds and filter decisions are
    weight-independent, and ``weights=None`` compiles the exact
    pre-weight program (uniform weights of 1.0 are bit-identical to
    it, since multiplying by 1.0f is exact).

    ``x2``: cached ``||x||^2`` row norms (computed once per fit by the
    callers); ``None`` falls back to the diff-form rowwise distance.
    The new centroids' ``||c||^2`` is computed here ONCE and returned
    (``MoveOut.c2``) so the caller can share it with the following
    candidate pass instead of recomputing it.

    ``refresh=False`` (the compact backend's in-pass placement, and the
    streaming step where the refresh belongs to the NEXT batch's
    ``stream_bounds``) skips the own-distance refresh entirely — the
    returned ``need`` is then the *maybe* mask (``ub > glb`` on
    drift-inflated bounds) and the refresh happens on the compacted
    survivor buffer inside :func:`compact_candidate_pass`
    (``refresh_ub=True``), so the full-N gather + rowwise pass
    disappears from the hot loop.

    ``onehot``: compute the centroid sums as the one-hot MXU product
    (see :func:`onehot_sums`, which decides it; unweighted only).

    Returns a :class:`MoveOut`.
    """
    with jax.named_scope("kpynq/centroid_sums"):
        if onehot:
            sums, bcounts = onehot_centroid_sums(points, assignments, k)
        else:
            sums, bcounts = centroid_sums(points, assignments, k,
                                          weights=weights)
    with jax.named_scope("kpynq/reduce"):
        sums = reducer.sums(sums)
        bcounts = reducer.add(bcounts)
    new_c, new_counts = update.apply(sums, bcounts, centroids, counts,
                                     decay)
    new_c2 = row_norms_sq(new_c)                       # once per iteration

    drift = jnp.linalg.norm(new_c - centroids, axis=-1)
    group_drift = jax.ops.segment_max(drift, groups, num_segments=n_groups)
    if update.clamp_gdrift:
        group_drift = jnp.maximum(group_drift, 0.0)
    shift = jnp.max(drift)
    ub = ub + drift[assignments]
    lb_dec = jnp.maximum(lb - group_drift[None, :], 0.0)
    glb = jnp.min(lb_dec, axis=1)
    maybe = ub > glb
    if refresh:
        with jax.named_scope("kpynq/refresh"):
            if x2 is None:
                d_own = rowwise_dists(points, new_c[assignments])
            else:
                own = new_c[assignments]
                d_own = jnp.sqrt(jnp.maximum(
                    x2 - 2.0 * jnp.sum(points.astype(jnp.float32) * own,
                                       axis=-1) + new_c2[assignments], 0.0))
            ub_t = jnp.where(maybe, d_own, ub)
            need = ub_t > glb
    else:
        ub_t = ub
        need = maybe
    return MoveOut(new_c, new_c2, new_counts, ub_t, lb_dec, need, shift,
                   jnp.sum(maybe.astype(jnp.float32)), drift, group_drift,
                   bcounts)


def cap_old_group(lb, old_group, changed, ub_t):
    """Yinyang's exactness cap: a point that left centroid b (``changed``)
    puts b back in its group's non-assigned pool at exact distance
    ``ub_t``, so the lower bound of b's group (``old_group``) becomes
    ``min(lb, ub_t)``; every other bound stays. A one-hot select over
    the (N, G) bounds, so it fuses into the elementwise pass that wrote
    them. The scatter-min ``lb.at[rows, old_group].min(...)`` it replaces
    (kept in :func:`repro.core.kmeans._filtered_step`, the reference)
    goes one row at a time on the TPU: 21 ms a pass at N = 2.46M, G = 5
    on one TPU v5e. Bit for bit the scatter-min's result.
    """
    hit = ((jnp.arange(lb.shape[1], dtype=old_group.dtype)[None, :]
            == old_group[:, None]) & changed[:, None])
    return jnp.where(hit, jnp.minimum(lb, ub_t[:, None]), lb)


def dense_candidate_pass(points, new_c, assignments, ub_t, lb, groups, need,
                         *, n_groups: int, opt_sq: bool = True,
                         x2=None, c2=None):
    """Masked-dense candidate pass over all N points (oracle backend and
    the per-shard distributed step). Group filter applied as a mask —
    exact semantics, no skipped FLOPs.

    ``opt_sq=True`` (default) runs min/argmin on SQUARED distances and
    sqrts only the reduced outputs (monotone => bit-identical results,
    one fewer (N, K) sqrt pass + HBM round-trip). ``x2``/``c2``:
    cached squared norms (see :mod:`repro.core.distances`).

    Returns ``(new_assign, new_ub, new_lb, n_pairs)``.
    """
    n = points.shape[0]
    rows = jnp.arange(n)
    group_need = need[:, None] & (lb < ub_t[:, None])              # (N, G)
    cand = group_need[:, groups]                                    # (N, K)
    pairs = jnp.sum(cand.astype(jnp.float32))

    if opt_sq:
        d_cand = jnp.where(cand, pairwise_sq_dists(points, new_c, x2, c2),
                           jnp.inf)
        best = jnp.argmin(d_cand, axis=1).astype(jnp.int32)
        best_d = jnp.sqrt(jnp.min(d_cand, axis=1))
    else:
        d_cand = jnp.where(cand, pairwise_dists(points, new_c, x2, c2),
                           jnp.inf)
        best = jnp.argmin(d_cand, axis=1).astype(jnp.int32)
        best_d = jnp.min(d_cand, axis=1)
    changed = best_d < ub_t
    new_assign = jnp.where(changed, best, assignments)
    new_ub = jnp.minimum(ub_t, best_d)

    d_excl = d_cand.at[rows, new_assign].set(jnp.inf)
    lb_comp = jax.ops.segment_min(d_excl.T, groups,
                                  num_segments=n_groups).T          # (N, G)
    if opt_sq:
        lb_comp = jnp.sqrt(lb_comp)
    new_lb = cap_old_group(jnp.where(group_need, lb_comp, lb),
                           groups[assignments], changed, ub_t)
    return new_assign, new_ub, new_lb, pairs


def compact_candidate_pass(points, new_c, assignments, ub_t, lb, groups,
                           members, gsize, need, *, cap_n: int, cap_g: int,
                           n_groups: int, chunk: int = 2048,
                           use_groups: bool | None = None,
                           opt_sq: bool = True, x2=None, c2=None,
                           refresh_ub: bool = False,
                           group_gather_factor: int = 4):
    """Two-level compacted candidate pass.

    Point level: the ``need`` survivors are stream-compacted into a
    ``cap_n`` buffer (``cap_n`` must be >= the survivor count — the
    engine's while-loop cond guarantees it).

    ``refresh_ub=True`` (the engine's compact backend): ``need`` is the
    *maybe* mask from :func:`move_and_bounds` ``refresh=False`` and the
    exact own-centroid distance is computed HERE, on the compacted
    buffer only — points whose refreshed bound re-filters them simply
    flow through with a tightened ``ub`` and an empty group set (their
    distance rows are masked out), so the full-N rowwise refresh is
    gone while the semantics stay bit-identical.

    Centroid level: each candidate's surviving groups are compacted
    into a ``cap_g``-slot bucket; only those groups' member centroids
    (``members``: (G, Lmax) int32, -1-padded) are gathered and scored.
    The gather-vs-GEMM crossover is :func:`use_groups_decision` (tuned
    via ``group_gather_factor`` / ``chunk`` — see
    :class:`EngineConfig`); ``use_groups=None`` applies it at trace
    time. When the bucket IS compiled in, a runtime ``lax.cond``
    spills to the dense branch whenever some candidate's
    surviving-group count exceeds ``cap_g`` — exactness never depends
    on the bucket guess; the engine reads the returned ``gmax`` to
    upshift the next segment.

    ``x2``/``c2``: cached squared norms (full-size ``x2`` is gathered
    per survivor; ``c2`` is this iteration's centroid norms from
    :func:`move_and_bounds`).

    Returns updated full-size ``(assignments, ub, lb, n_pairs, gmax)``.
    """
    n = points.shape[0]
    k = new_c.shape[0]
    l_max = members.shape[1]
    rows = jnp.arange(cap_n)

    # --- point-level compaction -------------------------------------
    pos = jnp.cumsum(need.astype(jnp.int32)) - 1
    slot = jnp.where(need, pos, cap_n)
    idx = jnp.zeros((cap_n,), jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    count = jnp.sum(need.astype(jnp.int32))
    valid = jnp.arange(cap_n) < count

    cpts = points[idx]                                        # (cap, D)
    c_ub = ub_t[idx]
    c_lb = lb[idx]                                            # (cap, G)
    c_as = assignments[idx]
    if c2 is None:
        c2 = row_norms_sq(new_c)
    c_x2 = x2[idx] if x2 is not None else row_norms_sq(cpts)  # (cap,)
    if refresh_ub:
        # own-distance refresh on the compacted buffer (cap_n rows, not
        # N): d(x, c_a) via the cached norms; invalid slots compute
        # garbage that the scatter drops
        own = new_c[c_as]
        c_ub = jnp.sqrt(jnp.maximum(
            c_x2 - 2.0 * jnp.sum(cpts.astype(jnp.float32) * own, axis=-1)
            + c2[c_as], 0.0))
    gneed = (c_lb < c_ub[:, None]) & valid[:, None]           # (cap, G)
    gmax = jnp.max(jnp.sum(gneed.astype(jnp.int32), axis=1))
    # rows that still need any distance work after the (possibly
    # in-pass) refresh — the dense branch's honest eval count
    n_rows = jnp.sum(jnp.any(gneed, axis=1).astype(jnp.float32))

    if use_groups is None:
        use_groups = use_groups_decision(
            cap_n=cap_n, cap_g=cap_g, l_max=l_max, k=k, chunk=chunk,
            group_gather_factor=group_gather_factor)

    def dense_branch(_):
        # one (cap_n, K) GEMM on the survivors
        gmask = gneed[:, groups]                              # (cap, K)
        if opt_sq:
            # min/argmin on squared distances (monotone => identical),
            # sqrt only the (cap,)/(cap, G) reductions: one fewer
            # (cap, K) sqrt pass per iteration.
            d_cand = jnp.where(gmask,
                               pairwise_sq_dists(cpts, new_c, c_x2, c2),
                               jnp.inf)
            bid = jnp.argmin(d_cand, axis=1).astype(jnp.int32)
            bd = jnp.sqrt(jnp.min(d_cand, axis=1))
        else:
            d_cand = jnp.where(gmask,
                               pairwise_dists(cpts, new_c, c_x2, c2),
                               jnp.inf)
            bid = jnp.argmin(d_cand, axis=1).astype(jnp.int32)
            bd = jnp.min(d_cand, axis=1)
        chg = bd < c_ub
        nas = jnp.where(chg, bid, c_as)
        nub = jnp.minimum(c_ub, bd)
        d_excl = d_cand.at[rows, nas].set(jnp.inf)
        lb_comp = jax.ops.segment_min(d_excl.T, groups,
                                      num_segments=n_groups).T
        if opt_sq:
            lb_comp = jnp.sqrt(lb_comp)
        new_clb = jnp.where(gneed, lb_comp, c_lb)
        pairs = n_rows * k
        return nas, nub, new_clb, pairs, chg

    def group_branch(_):
        # centroid-level compaction: padded per-point group bucket
        gpos = jnp.cumsum(gneed.astype(jnp.int32), axis=1) - 1
        gslot = jnp.where(gneed, gpos, cap_g)
        gsel = jnp.full((cap_n, cap_g), n_groups, jnp.int32).at[
            rows[:, None], gslot].set(
            jnp.broadcast_to(jnp.arange(n_groups, dtype=jnp.int32),
                             (cap_n, n_groups)), mode="drop")

        def bucket_pass(x, x2v, gs, cub, cas):
            mem = jnp.take(members, gs, axis=0, mode="fill",
                           fill_value=-1)                # (ch, cap_g, L)
            mem_s = jnp.maximum(mem, 0)
            csel = new_c[mem_s]                          # (ch, cap_g, L, D)
            xf = x.astype(jnp.float32)
            cross = jnp.einsum("nd,ngld->ngl", xf,
                               csel.astype(jnp.float32),
                               precision=CROSS_PRECISION)
            d2 = jnp.maximum(x2v[:, None, None] - 2.0 * cross + c2[mem_s],
                             0.0)
            ch = x.shape[0]
            # squared-distance reductions, sqrt only the outputs
            dm = jnp.where(mem >= 0, d2, jnp.inf).reshape(ch, -1)
            memf = mem.reshape(ch, -1)
            bcol = jnp.argmin(dm, axis=1)
            bd = jnp.sqrt(jnp.min(dm, axis=1))
            bid = jnp.take_along_axis(memf, bcol[:, None], 1)[:, 0]
            chg = bd < cub
            nas = jnp.where(chg, bid, cas).astype(jnp.int32)
            nub = jnp.minimum(cub, bd)
            d_ex = jnp.where(memf == nas[:, None], jnp.inf, dm)
            smin = jnp.sqrt(jnp.min(d_ex.reshape(ch, cap_g, l_max),
                                    axis=2))
            return nas, nub, smin, chg

        nas, nub, smin, chg = bucket_pass(cpts, c_x2, gsel, c_ub, c_as)
        new_clb = c_lb.at[rows[:, None], gsel].set(smin, mode="drop")
        pairs = jnp.sum(gneed.astype(jnp.float32) * gsize[None, :])
        return nas, nub, new_clb, pairs, chg

    if use_groups:
        nas, nub, new_clb, pairs, chg = jax.lax.cond(
            gmax <= cap_g, group_branch, dense_branch, operand=None)
    else:
        nas, nub, new_clb, pairs, chg = dense_branch(None)

    new_clb = cap_old_group(new_clb, jnp.take(groups, c_as), chg, c_ub)

    # --- scatter survivors back (invalid slots dropped) --------------
    sidx = jnp.where(valid, idx, n)
    assignments = assignments.at[sidx].set(nas, mode="drop")
    ub_out = ub_t.at[sidx].set(nub, mode="drop")
    lb_out = lb.at[sidx].set(new_clb, mode="drop")
    return assignments, ub_out, lb_out, pairs, gmax


def cap_ladders(n: int, n_groups: int, *, min_cap: int = 256,
                max_branches: int = 12):
    """Static (cap_n, cap_g) lattices for the IN-TRACE bucketed pass.

    The batch driver picks capacities on the host between ``_run_loop``
    segments; inside a ``shard_map`` body there is no host to ask, so
    the whole lattice must be fixed at trace time and the shard switches
    between levels with ``lax.switch`` (:func:`ladder_candidate_pass`).
    Levels are the engine's usual power-of-two lattice from ``min_cap``
    up to the shard size (resp. 1 up to ``n_groups``), coarsened until
    the branch product fits ``max_branches`` compiled pass instances:
    interior levels go first, then (only under a budget too small for
    2x2 ladders) the LOW endpoints. The top levels are never dropped —
    ``cap_ns[-1] == n`` is what makes the mandatory upshift in
    :func:`select_bucket` always able to satisfy the pass's
    ``cap_n >= count`` precondition.
    """
    n = max(int(n), 1)
    n_groups = max(int(n_groups), 1)
    cap_ns, c = [], min(_bucket_cap(min_cap, 1, n), n)
    while c < n:
        cap_ns.append(c)
        c *= 2
    cap_ns.append(n)
    cap_gs, g = [], 1
    while g < n_groups:
        cap_gs.append(g)
        g *= 2
    cap_gs.append(n_groups)
    while len(cap_ns) * len(cap_gs) > max(int(max_branches), 1):
        if len(cap_gs) > 2 and len(cap_gs) >= len(cap_ns):
            del cap_gs[len(cap_gs) // 2]
        elif len(cap_ns) > 2:
            del cap_ns[len(cap_ns) // 2]
        elif len(cap_gs) > 1:
            del cap_gs[0]
        elif len(cap_ns) > 1:
            del cap_ns[0]
        else:
            break
    return tuple(cap_ns), tuple(cap_gs)


def select_bucket(n_cand, gmax, level_n, level_g, *, cap_ns, cap_gs,
                  down_n: int = 2, down_g: int = 4):
    """Shard-local bucket transition — the traced analogue of the host
    bucket picker in :func:`fit`.

    Upshifts are mandatory the moment the pending candidate count (or
    the observed surviving-group high-water) leaves its level;
    downshifts only fire past the tuned hysteresis factors
    (``EngineConfig.down_n`` / ``down_g``; 0 disables that axis), and
    never on ``gmax == 0`` (no candidates seen — not evidence that one
    group slot suffices). Returns the next ``(level_n, level_g)``.
    """
    cn = jnp.asarray(cap_ns, jnp.int32)
    cg = jnp.asarray(cap_gs, jnp.int32)
    req_n = jnp.minimum(jnp.searchsorted(cn, n_cand),
                        len(cap_ns) - 1).astype(jnp.int32)
    move = req_n > level_n
    if down_n:
        move = jnp.logical_or(move, jnp.logical_and(
            req_n < level_n, n_cand * down_n <= cn[level_n]))
    new_n = jnp.where(move, req_n, level_n)

    req_g = jnp.minimum(jnp.searchsorted(cg, jnp.maximum(gmax, 1)),
                        len(cap_gs) - 1).astype(jnp.int32)
    move_g = req_g > level_g
    if down_g:
        move_g = jnp.logical_or(move_g, jnp.logical_and(
            jnp.logical_and(gmax > 0, req_g < level_g),
            gmax * down_g <= cg[level_g]))
    new_g = jnp.where(move_g, req_g, level_g)
    return new_n, new_g


def ladder_candidate_pass(points, new_c, assignments, ub_t, lb, groups,
                          members, gsize, need, level_n, level_g, *,
                          cap_ns, cap_gs, n_groups: int, chunk: int = 2048,
                          group_gather_factor: int = 4, opt_sq: bool = True,
                          x2=None, c2=None, refresh_ub: bool = False):
    """:func:`compact_candidate_pass` at a TRACED capacity level.

    One ``lax.switch`` over the static ``cap_ns`` x ``cap_gs`` lattice
    (:func:`cap_ladders`); each branch is the compact pass compiled at
    one (cap_n, cap_g) pair, with the gather-vs-GEMM crossover
    (:func:`use_groups_decision`) resolved per branch at trace time.
    This is what lets a ``shard_map`` body run the two-level compaction
    with SHARD-LOCAL bucket choices and zero host syncs: every shard
    executes only its selected branch, and no collectives live inside
    the branches so shards in different buckets cannot desynchronise.
    Correctness needs ``cap_ns[level_n] >= sum(need)`` — the mandatory
    upshift in :func:`select_bucket` maintains it; ``cap_g`` stays a
    guess (the pass's ``lax.cond`` spills to its dense branch).
    """
    branches = []
    for cn in cap_ns:
        for cg in cap_gs:
            def branch(_, cn=cn, cg=cg):
                return compact_candidate_pass(
                    points, new_c, assignments, ub_t, lb, groups, members,
                    gsize, need, cap_n=cn, cap_g=cg, n_groups=n_groups,
                    chunk=chunk, use_groups=None, opt_sq=opt_sq, x2=x2,
                    c2=c2, refresh_ub=refresh_ub,
                    group_gather_factor=group_gather_factor)
            branches.append(branch)
    if len(branches) == 1:
        return branches[0](None)
    index = level_n * len(cap_gs) + level_g
    return jax.lax.switch(index, branches, None)


def pallas_candidate_pass(points, new_c, assignments, ub_t, lb, groups,
                          members, gsize, need, *, n_groups: int,
                          tile_n: int = 256, interpret: bool = False,
                          x2=None, c2=None):
    """Candidate pass through the grouped block-skip Pallas kernel.

    The (point, group) filter decisions become a (N/tile_n, G) block
    mask; the kernel runs the distance matmul only for live blocks and
    returns the global (min, argmin) plus per-group (min, argmin,
    second-min) — exactly what the Yinyang lower-bound refresh needs,
    with no (N, K) distance matrix ever materialised. Cached squared
    norms (``x2`` per point, ``c2`` per centroid) are threaded into
    the kernel so it never recomputes them.
    """
    from ..kernels import build_group_block_mask, grouped_assign

    group_need = need[:, None] & (lb < ub_t[:, None])              # (N, G)
    mask = build_group_block_mask(group_need, tile_n=tile_n)       # (gn, G)
    mem_s = jnp.maximum(members, 0)
    c_grouped = new_c[mem_s]                                # (G, Lmax, D)
    c2g = None if c2 is None else c2[mem_s]                 # (G, Lmax)
    best2, idx, gmin, garg, gmin2 = grouped_assign(
        points, c_grouped, members, mask, tile_n=tile_n,
        interpret=interpret, x2=x2, c2g=c2g)

    best_d = jnp.sqrt(best2)
    changed = best_d < ub_t
    new_assign = jnp.where(changed, idx, assignments)
    new_ub = jnp.minimum(ub_t, best_d)

    # per-group min excluding the (new) assigned centroid: the group
    # argmin collides with the assignment iff the assignment came from
    # that group, in which case the second-min is the excluded min.
    lb_comp = jnp.sqrt(jnp.where(garg == new_assign[:, None], gmin2, gmin))
    new_lb = cap_old_group(jnp.where(group_need, lb_comp, lb),
                           groups[assignments], changed, ub_t)
    pairs = jnp.float32(tile_n) * jnp.sum(
        mask.astype(jnp.float32) * gsize[None, :])
    return new_assign, new_ub, new_lb, pairs


# --------------------------------------------------------------------------
# the device-resident loop
# --------------------------------------------------------------------------

class EngineCarry(NamedTuple):
    """while_loop carry. ``ub``/``lb``/``need`` describe the PENDING
    candidate pass (iteration ``iteration``'s second half), which the
    next loop body — or the epilogue — executes. ``x2`` is the
    fit-constant point norms; ``c2`` is the CURRENT centroids' norms
    (refreshed once per iteration by :func:`move_and_bounds`)."""
    iteration: jnp.ndarray    # int32: completed move+bounds iterations
    centroids: jnp.ndarray    # (K, D)
    c2: jnp.ndarray           # (K,) ||centroids||^2, once per iteration
    assignments: jnp.ndarray  # (N,)
    ub: jnp.ndarray           # (N,) tightened upper bounds
    lb: jnp.ndarray           # (N, G) decayed lower bounds
    x2: jnp.ndarray           # (N,) ||x||^2, computed ONCE per fit
    need: jnp.ndarray         # (N,) pending candidate mask
    n_cand: jnp.ndarray       # int32 = sum(need)
    gmax: jnp.ndarray         # int32 max surviving groups per candidate,
                              # as observed by the LAST executed pass
    shift: jnp.ndarray        # f32 max centroid drift
    evals: EvalCount
    ring: jnp.ndarray         # (ring_iters, N_COUNTERS) telemetry ring
                              # (see repro.obs.ring); (0, C) when off


@dataclasses.dataclass
class EngineStats:
    """Execution telemetry: the 'no per-iteration host sync' claim is
    checkable as ``host_syncs << n_iters``; ``use_groups`` records the
    gather-vs-GEMM decision per compact segment (parallel to
    ``caps_history``); ``x2_evals`` states the norm-carry contract of
    the constructed trace — ``||x||^2`` enters via ``EngineCarry.x2``
    so exactly one full-N norm computation exists per fit by
    construction (it is structural, not a runtime counter;
    ``tests/test_tune.py`` verifies it by counting real
    ``row_norms_sq`` calls); ``config`` is the resolved
    :class:`EngineConfig` actually used; ``interpret`` whether the
    Pallas kernel ran in the interpreter (always False off the pallas
    backend and on the TPU); ``compiles`` the programs lowered during
    the fit (:func:`repro.obs.compile_count`; through ``KMeans.fit``,
    its seeding included): each a compile or a persistent-cache load,
    so a non-zero count on a repeated fit of one shape is a recompile;
    ``onehot_sums`` whether the fit's centroid sums ran as the one-hot
    MXU product (:func:`onehot_sums`) rather than the scatter-adds.

    With observability enabled (``fit(obs=...)``) the stats carry the
    drained telemetry ring: ``ring`` is the trimmed
    ``(n_iters + 1, C)`` numpy buffer (column layout ``ring_columns``
    = :data:`repro.obs.ring.RING_COLUMNS`; final row = epilogue),
    ``init_evals`` the distance evals charged at filter-state init so
    ``init_evals + ring[:, evals].sum() == result.distance_evals``
    exactly. The distributed driver additionally fills
    ``shard_rings`` (S, n_iters + 1, C) — per-shard, pre-reduction —
    and ``shard_skew`` (per-iteration max/mean work imbalance)."""
    backend: str = ""
    n_iters: int = 0
    host_syncs: int = 0
    bucket_switches: int = 0
    caps_history: list = dataclasses.field(default_factory=list)
    use_groups: list = dataclasses.field(default_factory=list)
    x2_evals: int = 0
    config: dict = dataclasses.field(default_factory=dict)
    n_points: int = 0
    ring: np.ndarray | None = None
    ring_columns: tuple = RING_COLUMNS
    init_evals: float = 0.0
    shard_rings: np.ndarray | None = None
    shard_skew: np.ndarray | None = None
    interpret: bool = False
    compiles: int = 0
    onehot_sums: bool = False

    def telemetry(self) -> dict | None:
        """Headline ring summary (iters, mean candidate fraction, total
        evals, ...) — what the benchmark records per dataset. ``None``
        when the fit ran without the ring."""
        if self.ring is None:
            return None
        out = _obs_ring.summarize_ring(self.ring, self.n_points,
                                       init_evals=self.init_evals)
        if self.shard_skew is not None and len(self.shard_skew):
            out["mean_shard_skew"] = float(np.mean(self.shard_skew))
            out["max_shard_skew"] = float(np.max(self.shard_skew))
        return out

    def to_dict(self) -> dict:
        """JSON-serializable view (numpy rings -> nested lists), for
        event logs / benchmark payloads."""
        out = {
            "backend": self.backend,
            "n_iters": int(self.n_iters),
            "host_syncs": int(self.host_syncs),
            "bucket_switches": int(self.bucket_switches),
            "caps_history": [list(c) for c in self.caps_history],
            "use_groups": [bool(u) for u in self.use_groups],
            "x2_evals": int(self.x2_evals),
            "config": dict(self.config),
            "n_points": int(self.n_points),
            "interpret": bool(self.interpret),
            "compiles": int(self.compiles),
            "onehot_sums": bool(self.onehot_sums),
        }
        if self.ring is not None:
            out["ring_columns"] = list(self.ring_columns)
            out["ring"] = np.asarray(self.ring, np.float64).tolist()
            out["init_evals"] = float(self.init_evals)
            out["telemetry"] = self.telemetry()
        if self.shard_skew is not None:
            out["shard_skew"] = np.asarray(
                self.shard_skew, np.float64).tolist()
        return out


@dataclasses.dataclass(frozen=True)
class PassCore:
    """THE filtered-iteration core: one candidate-pass dispatch + one
    move/bounds epilogue, parameterised by a :class:`Reducer` — the
    single implementation behind ``engine.fit`` (local reducer,
    host-picked buckets), ``repro.core.distributed`` (psum reducer,
    in-trace capacity ladder) and ``repro.streaming`` (single step +
    EMA epilogue).

    ``backend``: the candidate-pass realisation — ``"oracle"``
    (masked dense), ``"compact"`` (two-level compaction at the static
    ``cap_n``/``cap_g``), ``"ladder"`` (compaction switched over the
    static ``cap_ns`` x ``cap_gs`` lattice with ``lax.switch`` —
    what a ``shard_map`` body runs, where a host bucket pick is not an
    option) or ``"pallas"`` (group-granular block-skip kernel).

    Frozen/hashable so a core is a jit-static argument: every field is
    a shape/dispatch choice, none affects the fixed point.
    """
    backend: str
    k: int
    n_groups: int
    reducer: Reducer = LOCAL_REDUCER
    cap_n: int = 0                 # static caps (compact backend)
    cap_g: int = 0
    cap_ns: tuple = ()             # capacity lattice (ladder backend)
    cap_gs: tuple = ()
    chunk: int = 2048
    tile_n: int = 256
    group_gather_factor: int = 4
    down_n: int = 2
    down_g: int = 4
    refresh_in_pass: bool = False
    use_groups: bool | None = None
    interpret: bool = False
    # opt_sq=False exists for analysis artifacts only (the dry-run's
    # A/B of the squared-distance reductions); every driver runs True
    opt_sq: bool = True
    # telemetry-ring rows carried through the loop (0 = ring disabled;
    # the drivers set max_iters + 1 so the epilogue gets the last row).
    # Shape/dispatch only — the ring never feeds back into the fit.
    ring_iters: int = 0
    # emit each ring row as it is written via io_callback (see
    # repro.obs.ring.add_ring_listener); requires ring_iters > 0
    live_drain: bool = False
    # centroid sums as the one-hot MXU product (see onehot_sums; the
    # batch fit resolves it, every other driver keeps the scatter)
    onehot_sums: bool = False

    @classmethod
    def from_config(cls, cfg: EngineConfig, *, backend: str, k: int,
                    n_groups: int, **kw) -> "PassCore":
        """Lift the tuned knobs of an :class:`EngineConfig` into a
        core; ``kw`` pins the per-driver fields (caps/ladder/reducer)."""
        return cls(backend=backend, k=k, n_groups=n_groups,
                   chunk=cfg.chunk, tile_n=cfg.tile_n,
                   group_gather_factor=cfg.group_gather_factor,
                   down_n=cfg.down_n, down_g=cfg.down_g,
                   refresh_in_pass=cfg.refresh_in_pass, **kw)

    @property
    def refresh_in_move(self) -> bool:
        """Where the own-distance refresh runs: in
        :func:`move_and_bounds` (full-N rowwise) unless the compacting
        backends place it on the survivor buffer."""
        return not (self.backend in ("compact", "ladder")
                    and self.refresh_in_pass)

    def candidate_pass(self, points, centroids, assignments, ub, lb, need,
                       groups, members, gsize, *, x2, c2,
                       level_n=None, level_g=None):
        """Backend dispatch, normalised to
        ``(assign, ub, lb, pairs, gmax)``."""
        if self.backend == "oracle":
            out = dense_candidate_pass(
                points, centroids, assignments, ub, lb, groups, need,
                n_groups=self.n_groups, opt_sq=self.opt_sq, x2=x2, c2=c2)
            return out + (jnp.int32(0),)
        if self.backend == "pallas":
            out = pallas_candidate_pass(
                points, centroids, assignments, ub, lb, groups, members,
                gsize, need, n_groups=self.n_groups, tile_n=self.tile_n,
                interpret=self.interpret, x2=x2, c2=c2)
            return out + (jnp.int32(0),)
        if self.backend == "ladder":
            return ladder_candidate_pass(
                points, centroids, assignments, ub, lb, groups, members,
                gsize, need, level_n, level_g, cap_ns=self.cap_ns,
                cap_gs=self.cap_gs, n_groups=self.n_groups,
                chunk=self.chunk,
                group_gather_factor=self.group_gather_factor, x2=x2,
                c2=c2, refresh_ub=self.refresh_in_pass)
        return compact_candidate_pass(
            points, centroids, assignments, ub, lb, groups, members,
            gsize, need, cap_n=self.cap_n, cap_g=self.cap_g,
            n_groups=self.n_groups, chunk=self.chunk,
            opt_sq=self.opt_sq, x2=x2, c2=c2,
            refresh_ub=self.refresh_in_pass, use_groups=self.use_groups,
            group_gather_factor=self.group_gather_factor)


def _ring_caps(core: PassCore, level_n, level_g, n: int):
    """The (cap_n, cap_g) the candidate pass actually ran at, as fp32
    ring values: the static caps on the compact backend, the traced
    lattice level on the ladder, N/G for the non-compacting passes."""
    if core.backend == "compact":
        return jnp.float32(core.cap_n), jnp.float32(core.cap_g)
    if core.backend == "ladder":
        return (jnp.take(jnp.asarray(core.cap_ns, jnp.float32), level_n),
                jnp.take(jnp.asarray(core.cap_gs, jnp.float32), level_g))
    return jnp.float32(n), jnp.float32(core.n_groups)


def _loop_body(core: PassCore, points, weights, groups, members, gsize):
    """THE candidate-pass loop body (pending candidate pass at the top,
    then move + bound maintenance through ``core.reducer``) — the one
    copy every driver iterates: ``lax.while_loop`` in ``_run_loop`` and
    :func:`fit_core`, python-unrolled in the dry-run analysis variant.
    State is ``(EngineCarry, level_n, level_g)``; the ladder backend
    transitions its levels shard-locally via :func:`select_bucket`,
    every other backend carries constant zeros.

    With ``core.ring_iters > 0`` each body additionally writes one row
    of the telemetry ring (``repro.obs.ring`` layout) at its iteration
    index — a (C,) scatter into loop-carried state, no host traffic;
    ``core.live_drain`` adds a one-way ``io_callback`` per iteration."""

    def body(state):
        c, ln, lg = state
        with jax.named_scope("kpynq/candidate_pass"):
            new_as, new_ub, new_lb, pairs, gmax = core.candidate_pass(
                points, c.centroids, c.assignments, c.ub, c.lb, c.need,
                groups, members, gsize, x2=c.x2, c2=c.c2, level_n=ln,
                level_g=lg)
        with jax.named_scope("kpynq/move_and_bounds"):
            mv = move_and_bounds(
                points, c.centroids, new_as, new_ub, new_lb, groups,
                k=core.k, n_groups=core.n_groups, reducer=core.reducer,
                weights=weights, x2=c.x2, refresh=core.refresh_in_move,
                onehot=core.onehot_sums)
        n_cand = jnp.sum(mv.need.astype(jnp.int32))
        ring = c.ring
        if core.ring_iters:
            with jax.named_scope("kpynq/ring_write"):
                cap_n, cap_g = _ring_caps(core, ln, lg, points.shape[0])
                proxy = mv.ub * mv.ub
                if weights is not None:
                    proxy = proxy * weights
                row = jnp.stack([
                    n_cand.astype(jnp.float32),
                    gmax.astype(jnp.float32),
                    mv.shift,
                    pairs + mv.tightened,
                    cap_n,
                    cap_g,
                    jnp.sum(proxy),
                    mv.tightened,
                ])
                ring = ring.at[c.iteration].set(row)
            if core.live_drain:
                io_callback(_obs_ring.emit_ring_row, None, c.iteration,
                            row, ordered=False)
        carry = EngineCarry(c.iteration + 1, mv.centroids, mv.c2, new_as,
                            mv.ub, mv.lb, c.x2, mv.need, n_cand, gmax,
                            mv.shift, c.evals.add(pairs).add(mv.tightened),
                            ring)
        if core.backend == "ladder":
            ln, lg = select_bucket(n_cand, gmax, ln, lg,
                                   cap_ns=core.cap_ns, cap_gs=core.cap_gs,
                                   down_n=core.down_n, down_g=core.down_g)
        return carry, ln, lg

    return body


def _loop_cond(core: PassCore, *, max_iters, tol, min_cap=0,
               allow_downshift=False):
    """The loop condition matching :func:`_loop_body`. Terminal exits
    (converged / out of iterations) for every backend — with a psum
    reducer the centroid sums are replicated, so ``shift`` agrees on
    every shard and the collectives stay in lockstep. The host-bucketed
    compact backend additionally exits when the pending candidate count
    leaves its bucket (or a strictly smaller bucket would fit), which
    is the batch driver's ONLY host sync."""

    def cond(state):
        c, _, _ = state
        active = jnp.logical_and(c.iteration < max_iters, c.shift > tol)
        if core.backend != "compact":
            return active
        fits = jnp.logical_and(c.n_cand <= core.cap_n,
                               c.gmax <= core.cap_g)
        ok = jnp.logical_and(active, fits)
        if allow_downshift and (core.down_n or core.down_g):
            # exit when a strictly smaller point bucket would fit — the
            # candidate pass is linear in cap_n, so one sync (~ms) buys
            # back every decay-phase iteration's padding. The group cap
            # only affects the bucketed pass's minor axis; chase it
            # lazily to avoid segment churn. The factors are the tuned
            # hysteresis (EngineConfig.down_n / down_g; 0 disables).
            down = jnp.bool_(False)
            if core.down_n:
                down = jnp.logical_or(down, jnp.logical_and(
                    c.n_cand * core.down_n <= core.cap_n,
                    core.cap_n > min_cap))
            if core.down_g:
                # gmax == 0 means the last pass saw no candidates, not
                # that one group slot suffices — never downshift on it
                down = jnp.logical_or(down, jnp.logical_and(
                    jnp.logical_and(c.gmax > 0,
                                    c.gmax * core.down_g <= core.cap_g),
                    core.cap_g > 1))
            ok = jnp.logical_and(ok, jnp.logical_not(down))
        return ok

    return cond


@functools.partial(jax.jit, static_argnames=(
    "core", "max_iters", "tol", "min_cap", "allow_downshift"))
def _run_loop(points, weights, carry, groups, members, gsize, *, core,
              max_iters, tol, min_cap, allow_downshift):
    """One capacity bucket's worth of device-resident iterations.

    Exits when converged / out of iterations (terminal), or — compact
    backend only — when the pending candidate count leaves its bucket
    ((cap/2, cap] for points, (cap/4, cap] for group slots), at which
    point the host picks the next bucket from the exit scalars. That
    is the ONLY host sync."""
    carry, _, _ = jax.lax.while_loop(
        _loop_cond(core, max_iters=max_iters, tol=tol, min_cap=min_cap,
                   allow_downshift=allow_downshift),
        _loop_body(core, points, weights, groups, members, gsize),
        (carry, jnp.int32(0), jnp.int32(0)))
    return carry


def _epilogue_pass(core: PassCore, points, weights, valid, carry, groups,
                   members, gsize, level_n, level_g):
    """Final pending candidate pass + (weighted) inertia — the traced
    tail shared by `_epilogue` and :func:`fit_core`. ``valid`` masks
    sentinel padding rows of an uneven sharded fit (their assignment is
    K; clip the gather and zero their cost).

    Returns ``(new_as, evals, inertia, ring)`` — the ring gains its
    final row at index ``carry.iteration``: the epilogue pass's evals
    and, in the inertia-proxy column, the EXACT (shard-local,
    pre-reduction) inertia."""
    with jax.named_scope("kpynq/candidate_pass"):
        new_as, _, _, pairs, _ = core.candidate_pass(
            points, carry.centroids, carry.assignments, carry.ub, carry.lb,
            carry.need, groups, members, gsize, x2=carry.x2, c2=carry.c2,
            level_n=level_n, level_g=level_g)
    evals = core.reducer.add(carry.evals.add(pairs).total())
    with jax.named_scope("kpynq/inertia"):
        own = carry.centroids[jnp.minimum(new_as, core.k - 1)]
        d = rowwise_dists(points, own)
        d2 = d * d
        if valid is not None:
            d2 = jnp.where(valid, d2, 0.0)
        if weights is not None:
            d2 = d2 * weights
        local_inertia = jnp.sum(d2)
        inertia = core.reducer.add(local_inertia)
    ring = carry.ring
    if core.ring_iters:
        with jax.named_scope("kpynq/ring_write"):
            cap_n, cap_g = _ring_caps(core, level_n, level_g,
                                      points.shape[0])
            row = jnp.stack([
                carry.n_cand.astype(jnp.float32),
                carry.gmax.astype(jnp.float32),
                carry.shift,
                pairs,
                cap_n,
                cap_g,
                local_inertia,
                jnp.float32(0.0),
            ])
            ring = ring.at[carry.iteration].set(row)
        if core.live_drain:
            io_callback(_obs_ring.emit_ring_row, None, carry.iteration,
                        row, ordered=False)
    return new_as, evals, inertia, ring


@functools.partial(jax.jit, static_argnames=("core",))
def _epilogue(points, weights, carry, groups, members, gsize, *, core):
    """Final pending candidate pass + inertia, fused into one program."""
    return _epilogue_pass(core, points, weights, None, carry, groups,
                          members, gsize, jnp.int32(0), jnp.int32(0))


def fit_core(points, init_c, groups, members, gsize, *, core: PassCore,
             max_iters: int, tol: float, weights=None, valid=None):
    """The WHOLE fit — init, candidate-pass loop, epilogue — as one
    traced function with zero host syncs: the driver body shared by the
    fused small-problem path (local reducer, full static caps) and the
    ``shard_map`` body in :mod:`repro.core.distributed` (psum reducer +
    ladder backend). ``valid`` masks sentinel padding rows of an uneven
    sharded fit (assignment K drops out of every segment_sum; ub=0 /
    lb=inf keeps them filtered forever, and their K initial distance
    rows are taken back out of the eval count); ``weights`` are
    per-point sample weights (see :func:`move_and_bounds`).

    Returns ``(centroids, assignments, n_iters, evals, inertia, ring)``
    — the ring is the (core.ring_iters, C) telemetry buffer (shape
    (0, C) when disabled), SHARD-LOCAL under ``shard_map``.
    """
    k = core.k
    carry = _init_carry(points, init_c, groups, n_groups=core.n_groups,
                        ring_iters=core.ring_iters)
    if valid is not None:
        pad = jnp.sum(1.0 - valid.astype(jnp.float32))
        carry = carry._replace(
            assignments=jnp.where(valid, carry.assignments, k),
            ub=jnp.where(valid, carry.ub, 0.0),
            lb=jnp.where(valid[:, None], carry.lb, jnp.inf),
            evals=carry.evals.add(-pad * k))
    state = (carry, jnp.int32(0), jnp.int32(0))
    carry, ln, lg = jax.lax.while_loop(
        _loop_cond(core, max_iters=max_iters, tol=tol),
        _loop_body(core, points, weights, groups, members, gsize), state)
    new_as, evals, inertia, ring = _epilogue_pass(
        core, points, weights, valid, carry, groups, members, gsize, ln,
        lg)
    return carry.centroids, new_as, carry.iteration, evals, inertia, ring


def fit_core_unrolled(points, init_c, groups, members, gsize, *,
                      core: PassCore, n_iters: int, weights=None):
    """:func:`fit_core` with the while_loop replaced by exactly
    ``n_iters`` python iterations of the SAME :func:`_loop_body` —
    analysis artifacts only (XLA cost_analysis does not descend into
    while bodies; the N-vs-(N-1) unrolled diff gives the exact
    per-iteration cost)."""
    carry = _init_carry(points, init_c, groups, n_groups=core.n_groups,
                        ring_iters=core.ring_iters)
    state = (carry, jnp.int32(0), jnp.int32(0))
    body = _loop_body(core, points, weights, groups, members, gsize)
    for _ in range(n_iters):
        state = body(state)
    carry, ln, lg = state
    new_as, evals, inertia, ring = _epilogue_pass(
        core, points, weights, None, carry, groups, members, gsize, ln,
        lg)
    return carry.centroids, new_as, carry.iteration, evals, inertia, ring


@functools.partial(jax.jit, static_argnames=("n_groups", "ring_iters"))
def _init_carry(points, init_c, groups, *, n_groups, ring_iters=0):
    """Fused setup: point norms (THE once-per-fit ``||x||^2``), initial
    filter state, and the initial loop carry — one dispatch instead of
    the ~8 eager ops the old driver issued per fit. ``ring_iters``
    sizes the telemetry ring (0 = disabled, a (0, C) array that makes
    every ring op in the loop free)."""
    n = points.shape[0]
    x2 = row_norms_sq(points)
    c2 = row_norms_sq(init_c.astype(jnp.float32))
    with jax.named_scope("kpynq/init"):      # the dense first assignment
        state0 = _init_filter_state(points, init_c, groups, n_groups,
                                    x2=x2, c2=c2)
    return EngineCarry(
        jnp.int32(0), state0.centroids, c2, state0.assignments, state0.ub,
        state0.lb, x2, jnp.zeros((n,), bool), jnp.int32(0), jnp.int32(0),
        jnp.float32(jnp.inf), state0.distance_evals,
        jnp.zeros((ring_iters, N_COUNTERS), jnp.float32))


@functools.partial(jax.jit, static_argnames=("core", "max_iters", "tol"))
def _fit_fused(points, init_c, weights, *, core, max_iters, tol):
    """Whole fit — grouping, init, loop, epilogue — as ONE program.

    Used for small problems (and exercised by tests for every backend):
    at a few thousand points the ~10 eager setup dispatches of the
    bucketed driver cost more than the entire fit, so run a single
    full-capacity segment with the group-membership table built on
    device (Lmax = K upper bound; fine at small K). Reuses
    :func:`fit_core` — at full capacities the loop's bucket conditions
    are vacuous, so the whole fit inlines to one program."""
    k, n_groups = core.k, core.n_groups
    groups = group_centroids(init_c, n_groups)
    # device-side (G, K) membership table: row g lists group g's
    # centroids in ascending order, -1-padded
    order = jnp.argsort(groups, stable=True)
    sg = groups[order]
    starts = jnp.searchsorted(sg, jnp.arange(n_groups))
    rank = jnp.arange(k) - starts[sg]
    members = jnp.full((n_groups, k), -1, jnp.int32).at[
        sg, rank].set(order.astype(jnp.int32))
    gsize = jax.ops.segment_sum(jnp.ones((k,), jnp.float32), groups,
                                num_segments=n_groups)
    return fit_core(points, init_c, groups, members, gsize, core=core,
                    max_iters=max_iters, tol=tol, weights=weights)


def _bucket_cap(count: int, floor: int, ceil: int) -> int:
    """Smallest power-of-two >= count, clamped to [floor, ceil]. The
    lattice keeps the set of compiled programs small and reusable."""
    cap = 1 << (max(int(count), 1) - 1).bit_length()
    return max(min(cap, ceil), min(floor, ceil))


def build_assign_tables(centroids, n_groups: int | None = None):
    """Group map + host-built tables over FIXED centroids — THE one
    copy of the inference-side table recipe (K//10 group heuristic,
    clamp to K, :func:`group_centroids`, :func:`build_group_tables`),
    shared by :func:`assign` and the estimator caches.

    Returns ``(groups, members, gsize)``.
    """
    k = centroids.shape[0]
    if n_groups is None:
        n_groups = max(k // 10, 1)
    n_groups = int(min(max(n_groups, 1), k))
    groups = group_centroids(centroids, n_groups)
    groups_np = np.asarray(jax.device_get(groups))
    members, gsize = build_group_tables(groups_np, n_groups)
    return groups, members, gsize


def build_group_tables(groups_np: np.ndarray, n_groups: int):
    """Host-side group tables: (G, Lmax) -1-padded membership matrix +
    fp32 group sizes. Shared by the batch fit and the streaming step."""
    counts = np.bincount(groups_np, minlength=n_groups)
    l_max = max(int(counts.max()), 1)
    members_np = np.full((n_groups, l_max), -1, np.int32)
    for g in range(n_groups):
        ids = np.nonzero(groups_np == g)[0]
        members_np[g, :len(ids)] = ids
    return jnp.asarray(members_np), jnp.asarray(counts.astype(np.float32))


def _resolve_config(*, backend, tile_n, min_cap, chunk, config, tune,
                    n, k, d):
    """Resolve the effective :class:`EngineConfig` for this fit.

    Precedence per knob: explicit ``fit`` kwarg > explicit ``config``
    object > tuned cache entry (``tune != "off"``) > built-in default.
    The caller's ``backend`` always wins unless it is ``"auto"``.
    Returns ``(config, resolved_backend)`` where the backend may be
    ``"lloyd"``.
    """
    cfg = DEFAULT_CONFIG
    if config is None and tune != "off":
        # "force" has already run the search by the time we get here
        # (fit() materialises it into an explicit config); both active
        # modes consult the persistent cache.
        from .. import tune as _tune
        cfg = _tune.lookup(n=n, k=k, d=d) or cfg
    if config is not None:
        cfg = config
    over = {}
    if tile_n is not None:
        over["tile_n"] = int(tile_n)
    if min_cap is not None:
        over["min_cap"] = int(min_cap)
    if chunk is not None:
        over["chunk"] = int(chunk)
    if over:
        cfg = cfg.replace(**over)

    resolved = backend
    if resolved == "auto":
        resolved = cfg.backend
    if resolved == "auto":
        if n * k <= cfg.lloyd_max_work:
            resolved = "lloyd"
        else:
            resolved = "pallas" if jax.default_backend() == "tpu" \
                else "compact"
    return cfg, resolved


def _publish_fit(obs_cfg, stats: EngineStats, result) -> None:
    """Publish one finished fit into the configured metrics registry —
    counters + an ``engine_fit`` event carrying the ring summary. Host
    python on already-fetched values; runs only under ``obs=``."""
    reg = obs_cfg.resolve_registry()
    labels = {"backend": stats.backend}
    reg.counter("engine_fits_total", "completed engine fits",
                labels=labels).inc()
    reg.counter("engine_distance_evals_total",
                "distance evaluations across fits", labels=labels).inc(
        float(result.distance_evals))
    reg.gauge("engine_last_n_iters", "iterations of the last fit",
              labels=labels).set(float(stats.n_iters))
    reg.gauge("engine_last_host_syncs", "host syncs of the last fit",
              labels=labels).set(float(stats.host_syncs))
    reg.counter("engine_compiles_total",
                "programs lowered (compiled or loaded) during fits",
                labels=labels).inc(float(stats.compiles))
    evt = {"backend": stats.backend, "n_iters": stats.n_iters,
           "host_syncs": stats.host_syncs, "compiles": stats.compiles,
           "onehot_sums": stats.onehot_sums,
           "n_points": stats.n_points,
           "distance_evals": float(result.distance_evals),
           "inertia": float(result.inertia)}
    tel = stats.telemetry()
    if tel is not None:
        evt["telemetry"] = tel
    reg.log_event("engine_fit", **evt)


def fit(points, init_centroids, *, n_groups: int | None = None,
        max_iters: int = 100, tol: float = 1e-4, backend: str = "auto",
        tile_n: int | None = None, min_cap: int | None = None,
        chunk: int | None = None, interpret: bool | None = None,
        max_bucket_switches: int = 32, return_stats: bool = False,
        config: EngineConfig | None = None, tune: str = "auto",
        sample_weight=None, obs=None, compiles_since: int | None = None):
    """Run filtered K-means fully device-resident.

    See the module docstring for backend semantics. ``interpret=None``
    resolves through :func:`repro.platform.pallas_interpret`: compiled
    on the TPU, interpreted (slowly) on the CPU backend.

    ``config`` pins an explicit :class:`EngineConfig`; ``tune``
    controls the per-(platform, N, K, D) autotuning cache
    (:mod:`repro.tune`): ``"auto"`` (default) uses a cached winner when
    one exists, ``"force"`` additionally runs the measured search on a
    cache miss and persists the result, ``"off"`` uses built-in
    defaults. Tuning changes wall-clock only — assignments and inertia
    are bit-identical across configurations. Individual kwargs
    (``tile_n``/``min_cap``/``chunk``) override both.

    ``sample_weight``: optional (N,) per-point weights, entering the
    centroid sums and the inertia only (bounds and filter decisions
    are weight-independent). ``None`` compiles the exact pre-weight
    program; uniform weights of 1.0 are bit-identical to it.

    ``obs``: observability switch (see :mod:`repro.obs`) — ``None`` /
    ``False`` disabled (the exact pre-obs program compiles), ``True``
    defaults, a ``MetricsRegistry`` or ``ObsConfig`` for control. When
    enabled, the per-iteration telemetry ring rides the loop carry and
    is drained ONCE at exit into ``EngineStats.ring``
    (``host_syncs`` is unchanged — the drain rides the exit fetch),
    and the fit publishes counters + an ``engine_fit`` event into the
    registry. Results are bit-identical with obs on or off.

    ``compiles_since``: a :func:`repro.obs.compile_count` reading from
    which ``EngineStats.compiles`` counts (default: this call's start;
    ``KMeans.fit`` passes the reading from before its seeding).

    Returns a :class:`~repro.core.kmeans.KMeansResult`; with
    ``return_stats=True`` returns ``(result, EngineStats)``.
    """
    if backend not in BACKENDS + ("auto", "lloyd"):
        raise ValueError(f"unknown engine backend {backend!r}; "
                         f"expected one of "
                         f"{BACKENDS + ('auto', 'lloyd')}")
    if tune not in ("auto", "off", "force"):
        raise ValueError(f"unknown tune mode {tune!r}; expected "
                         f"'auto', 'off' or 'force'")
    if compiles_since is None:
        compiles_since = compile_count()
    points = jnp.asarray(points)
    init_c = jnp.asarray(init_centroids)
    if init_c.dtype != jnp.float32:
        init_c = init_c.astype(jnp.float32)
    k = init_c.shape[0]
    n, d = points.shape
    weights = None if sample_weight is None else \
        jnp.asarray(sample_weight, jnp.float32)
    obs_cfg = normalize_obs(obs)
    ring_iters = int(max_iters) + 1 if obs_cfg and obs_cfg.ring else 0
    live_drain = bool(obs_cfg and obs_cfg.live_drain and ring_iters)

    if tune == "force" and config is None:
        from .. import tune as _tune
        config = _tune.get_or_tune(
            points, init_c, n_groups=n_groups, max_iters=int(max_iters),
            tol=float(tol))
    cfg, backend = _resolve_config(
        backend=backend, tile_n=tile_n, min_cap=min_cap, chunk=chunk,
        config=config, tune=tune, n=n, k=k, d=d)

    if backend == "lloyd":
        res = _lloyd_jit(points, init_c, weights, max_iters=int(max_iters),
                         tol=float(tol))
        if not return_stats and obs_cfg is None:
            return res              # keep the tiny-problem route lean:
                                    # no stats blocking / dict building
        stats = EngineStats(backend="lloyd", n_iters=int(res.n_iters),
                            host_syncs=1, config=cfg.to_dict(),
                            n_points=n,
                            compiles=compile_count() - compiles_since)
        if obs_cfg is not None:
            # the dense loop has no filter pass, hence no ring — the
            # registry still gets the fit event/counters
            _publish_fit(obs_cfg, stats, res)
        return (res, stats) if return_stats else res
    if interpret is None:
        interpret = backend == "pallas" and pallas_interpret()
    if n_groups is None:
        n_groups = max(k // 10, 1)
    n_groups = int(min(n_groups, k))
    tol = float(tol)

    stats = EngineStats(
        backend=backend, x2_evals=1, config=cfg.to_dict(), n_points=n,
        interpret=bool(interpret),
        onehot_sums=onehot_sums(k, d, platform=jax.default_backend(),
                                weighted=weights is not None))
    cap_floor = min(cfg.min_cap, n)

    def _core(cap_n, cap_g, l_max):
        ug = use_groups_decision(
            cap_n=cap_n, cap_g=cap_g, l_max=l_max, k=k, chunk=cfg.chunk,
            group_gather_factor=cfg.group_gather_factor) \
            if backend == "compact" else None
        return PassCore.from_config(
            cfg, backend=backend, k=k, n_groups=n_groups, cap_n=cap_n,
            cap_g=cap_g, use_groups=ug, interpret=bool(interpret),
            ring_iters=ring_iters, live_drain=live_drain,
            onehot_sums=stats.onehot_sums)

    def _drain_ring(ring):
        # one device_get at fit exit — rides the exit fetch the driver
        # does anyway, so host_syncs stays exactly as without obs
        stats.ring = np.asarray(jax.device_get(ring))[:stats.n_iters + 1]
        stats.init_evals = float(n) * k

    if n <= 4 * cap_floor:
        # small problem: eager setup + bucket churn costs more than the
        # whole fit — run the fully-fused single-program path
        core = _core(n, n_groups, k)
        c, a, it, evals, inertia, ring = _fit_fused(
            points, init_c, weights, core=core, max_iters=int(max_iters),
            tol=tol)
        stats.host_syncs = 1
        stats.n_iters = int(it)
        if backend == "compact":
            stats.caps_history.append((n, n_groups))
            stats.use_groups.append(bool(core.use_groups))
        result = KMeansResult(c, a, it, evals, inertia)
        if ring_iters:
            _drain_ring(ring)
        stats.compiles = compile_count() - compiles_since
        if obs_cfg is not None:
            _publish_fit(obs_cfg, stats, result)
        return (result, stats) if return_stats else result

    with span("kpynq.tables"):
        groups = group_centroids(init_c, n_groups)
        # group membership table (G, Lmax), -1-padded; one setup-time sync
        groups_np = np.asarray(jax.device_get(groups))
        stats.host_syncs += 1
        members, gsize = build_group_tables(groups_np, n_groups)
    l_max = int(members.shape[1])

    carry = _init_carry(points, init_c, groups, n_groups=n_groups,
                        ring_iters=ring_iters)

    # start tiny: the first loop body's pending candidate pass is empty
    # (carry.need = 0), so a full-capacity program would burn one whole
    # dense pass on padding. The first real candidate count exits the
    # loop after iteration 1 and picks the right bucket.
    cap_n, cap_g = cap_floor, 1
    while True:
        core = _core(cap_n, cap_g, l_max)
        stats.caps_history.append((cap_n, cap_g))
        if backend == "compact":
            stats.use_groups.append(bool(core.use_groups))
        allow_down = stats.bucket_switches < max_bucket_switches
        with span("kpynq.loop"):
            carry = _run_loop(points, weights, carry, groups, members,
                              gsize, core=core, max_iters=int(max_iters),
                              tol=tol, min_cap=cap_floor,
                              allow_downshift=allow_down)
            it, nc, gm, sh = jax.device_get(
                (carry.iteration, carry.n_cand, carry.gmax, carry.shift))
        stats.host_syncs += 1
        if int(it) >= max_iters or float(sh) <= tol:
            break
        if backend != "compact":          # single-trace backends never
            break                         # exit the loop non-terminally
        stats.bucket_switches += 1
        if stats.bucket_switches >= max_bucket_switches:
            cap_n, cap_g = _bucket_cap(n, cap_floor, n), n_groups
        else:
            cap_n = _bucket_cap(int(nc), cap_floor, n)
            # gmax == 0 means no candidate pass has run at this bucket
            # yet (the opening probe segment): guess the full group
            # count rather than burning a whole segment discovering it
            cap_g = _bucket_cap(int(gm), 1, n_groups) if int(gm) > 0 \
                else n_groups
    stats.n_iters = int(it)

    # epilogue: the final iteration's pending candidate pass + inertia.
    # Caps only key the compact pass; pin them for the single-trace
    # backends so the epilogue compiles exactly once.
    if backend == "compact":
        ecap_n = _bucket_cap(int(nc), cap_floor, n)
        ecap_g = _bucket_cap(int(gm), 1, n_groups)
    else:
        ecap_n, ecap_g = n, n_groups
    with span("kpynq.epilogue"):
        assignments, evals, inertia, ring = _epilogue(
            points, weights, carry, groups, members, gsize,
            core=_core(ecap_n, ecap_g, l_max))

    result = KMeansResult(carry.centroids, assignments, carry.iteration,
                          evals, inertia)
    if ring_iters:
        _drain_ring(ring)
    stats.compiles = compile_count() - compiles_since
    if obs_cfg is not None:
        _publish_fit(obs_cfg, stats, result)
    if return_stats:
        return result, stats
    return result


# --------------------------------------------------------------------------
# streaming / mini-batch single-pass step (driven by repro.streaming)
# --------------------------------------------------------------------------

class StreamStepOut(NamedTuple):
    """Outputs of one mini-batch :func:`stream_step`. The
    returned ``ub``/``lb`` are already decayed by this step's centroid
    drift, i.e. valid against the RETURNED centroids — exactly what the
    caller's per-shard bound cache wants to store."""
    centroids: jnp.ndarray    # (K, D) after the decayed update
    counts: jnp.ndarray       # (K,) decayed effective counts
    assignments: jnp.ndarray  # (B,)
    ub: jnp.ndarray           # (B,) post-move upper bounds
    lb: jnp.ndarray           # (B, G) post-move lower bounds
    pairs: jnp.ndarray        # f32: point-centroid pairs scored
    gmax: jnp.ndarray         # int32: surviving-group high-water
    drift: jnp.ndarray        # (K,) this step's per-centroid drift
    gdrift: jnp.ndarray       # (G,) this step's per-group max drift
    batch_counts: jnp.ndarray  # (K,) points of THIS batch per centroid
    batch_cost: jnp.ndarray   # f32 sum(ub^2) pre-move: an upper-bound
                              # estimate of the batch's inertia


@jax.jit
def stream_bounds(points, centroids, assignments, ub, lb):
    """Point-level filter over CARRIED (drift-inflated) bounds — the
    first half of ``move_and_bounds`` without the centroid move. ``ub``
    must upper-bound d(x, centroids[assignments]) and ``lb`` must
    lower-bound the per-group min excluding the assignment (the shard
    cache's :func:`repro.streaming.inflate_bounds` contract).

    Returns ``(ub_t, need, n_cand, n_tightened)``: tightened upper
    bounds, the pending candidate mask, its popcount, and how many
    exact own-centroid distances were spent tightening.
    """
    glb = jnp.min(lb, axis=1)
    maybe = ub > glb
    d_own = rowwise_dists(points, centroids[assignments])
    ub_t = jnp.where(maybe, d_own, ub)
    need = ub_t > glb
    return ub_t, need, jnp.sum(need.astype(jnp.int32)), jnp.sum(
        maybe.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("core",))
def stream_step(points, centroids, counts, decay, groups, members, gsize,
                assignments, ub_t, lb, need, weights=None, *,
                core: PassCore):
    """One mini-batch against EXTERNAL carry (centroids + effective
    counts): the PassCore candidate pass, then the decayed
    count-weighted centroid EMA (:data:`EMA_UPDATE` through
    :func:`move_and_bounds`), then post-move bound decay — the same
    pass + epilogue pieces as the batch drivers, instantiated with the
    streaming update rule.

    This is the reusable single-pass step behind
    :class:`repro.streaming.StreamingKMeans`; with a psum
    ``core.reducer`` it is also the body of the sharded step
    (``repro.core.distributed.make_stream_update_sharded``): the
    reducer joins the batch sums/counts so the EMA (and drift) come
    out replicated, and reduces the scalar telemetry
    (``pairs``/``gmax``/``batch_cost``).

    ``core.cap_n`` MUST be >= the (per-shard) candidate count (the
    caller syncs it via :func:`stream_bounds`); ``core.cap_g`` is a
    guess — the pass's ``lax.cond`` spills to the dense branch when it
    is exceeded, and the returned ``gmax`` recalibrates the next
    visit. ``weights``: optional per-point sample weights entering the
    batch sums/counts (the EMA's effective mass) and the batch cost.

    Sentinel-padded rows (sharded caller) carry assignment K: the
    traced drift gather clamps, and the caller slices their ub/lb off.
    """
    x2 = row_norms_sq(points)                 # once per batch
    c2 = row_norms_sq(centroids)
    new_as, nub, nlb, pairs, gmax = core.candidate_pass(
        points, centroids, assignments, ub_t, lb, need, groups, members,
        gsize, x2=x2, c2=c2)
    mv = move_and_bounds(
        points, centroids, new_as, nub, nlb, groups, k=core.k,
        n_groups=core.n_groups, reducer=core.reducer, update=EMA_UPDATE,
        counts=counts, decay=decay, weights=weights, refresh=False)
    cost = nub * nub if weights is None else weights * nub * nub
    return StreamStepOut(mv.centroids, mv.counts, new_as, mv.ub, mv.lb,
                         core.reducer.add(pairs), core.reducer.max(gmax),
                         mv.drift, mv.gdrift, mv.batch_counts,
                         core.reducer.add(jnp.sum(cost)))


# --------------------------------------------------------------------------
# tiled assignment (predict / transform / score drive this)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("core",))
def _assign_tile(points, centroids, c2, groups, members, gsize, *,
                 core: PassCore):
    """Exact nearest-centroid assignment of ONE tile through the
    PassCore candidate pass with vacuous bounds — norm-cached
    (``c2`` once per assign, ``x2`` per tile), never materialising an
    (N, K) matrix beyond the tile."""
    b = points.shape[0]
    x2 = row_norms_sq(points)
    a0 = jnp.zeros((b,), jnp.int32)
    ub = jnp.full((b,), jnp.inf, jnp.float32)
    lb = jnp.zeros((b, core.n_groups), jnp.float32)
    need = jnp.ones((b,), bool)
    nas, nub, _, pairs, _ = core.candidate_pass(
        points, centroids, a0, ub, lb, need, groups, members, gsize,
        x2=x2, c2=c2)
    return nas, nub, pairs


def assign(points, centroids, *, n_groups: int | None = None,
           groups=None, members=None, gsize=None, tile_n: int = 8192,
           chunk: int = 2048, group_gather_factor: int = 4):
    """Tiled exact nearest-centroid assignment against fixed centroids.

    The inference-side counterpart of the fit drivers: each ``tile_n``
    slice of ``points`` runs the PassCore compact candidate pass with
    vacuous bounds, so no O(N*K) distance buffer ever exists (the
    per-tile working set is (tile_n, K)) and the centroid norms are
    computed once for the whole call. ``KMeans.predict`` /
    ``StreamingKMeans.predict`` / ``score`` all land here.

    ``groups``/``members``/``gsize`` may be passed when the caller
    already holds the group tables (the streaming estimator does);
    otherwise they are built from the centroids (``n_groups`` defaults
    to the K//10 heuristic).

    Returns ``(labels, dists)``: (N,) int32 assignments and (N,) f32
    exact distances to the assigned centroid.
    """
    points = jnp.asarray(points)
    if points.dtype != jnp.float32:
        points = points.astype(jnp.float32)
    centroids = jnp.asarray(centroids)
    if centroids.dtype != jnp.float32:
        centroids = centroids.astype(jnp.float32)
    n = points.shape[0]
    k = centroids.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32))
    if groups is None:
        groups, members, gsize = build_assign_tables(centroids, n_groups)
    n_groups = int(gsize.shape[0])

    c2 = row_norms_sq(centroids)
    tile = min(_bucket_cap(min(tile_n, n), 1, n), n)
    core = PassCore(backend="compact", k=k, n_groups=n_groups,
                    cap_n=tile, cap_g=n_groups, chunk=chunk,
                    group_gather_factor=group_gather_factor)
    labels, dists = [], []
    for lo in range(0, n, tile):
        part = points[lo:lo + tile]
        if part.shape[0] < tile:      # pad the ragged tail tile so the
            part = jnp.pad(           # per-tile program compiles once
                part, ((0, tile - part.shape[0]), (0, 0)))
        nas, nub, _ = _assign_tile(part, centroids, c2, groups, members,
                                   gsize, core=core)
        labels.append(nas)
        dists.append(nub)
    labels = jnp.concatenate(labels)[:n]
    dists = jnp.concatenate(dists)[:n]
    return labels, dists


# --------------------------------------------------------------------------
# serve-side batched assignment (repro.serve drives this)
# --------------------------------------------------------------------------
#
# The serving hot path differs from `assign` in three ways:
#
# * centroids/norms are RUNTIME ARGUMENTS, not trace constants — the
#   double-buffered epoch swap (repro.serve.CentroidIndex) republishes
#   centroids continuously, and a publish must never recompile. The
#   compiled-program cache is keyed on the query bucket shape only.
# * the reduction is the min-trick, not argmin: XLA's row-wise argmin
#   does not vectorise when reducing the minor axis on CPU (it costs
#   ~8x the distance GEMM at K=64); `min` does. Two vectorised min
#   passes — the distance minimum, then the smallest index attaining
#   it — reproduce argmin's first-match semantics exactly, so labels
#   stay bit-identical to the dense oracle.
# * batches arrive pre-padded to a pow2 bucket, so there is no ragged
#   tail handling here; `lax.map` over `chunk`-point tiles keeps the
#   per-tile (chunk, K) working set cache-resident.

def _serve_fused_impl(q, centroids, c2, *, chunk: int = 1024):
    """Fused dense batched assignment: norm-cached distance GEMM +
    min-trick label reduction, tiled by ``chunk``. Exact (bit-identical
    to ``argmin`` of the dense distance matrix). Returns (B,) int32."""
    k = centroids.shape[0]
    iota = jnp.arange(k, dtype=jnp.int32)

    def tile_fn(qt):
        # ||x||^2 omitted: constant per row, argmin-invariant
        d2 = c2[None, :] - 2.0 * jnp.dot(qt, centroids.T,
                                         precision=CROSS_PRECISION)
        mn = jnp.min(d2, axis=1, keepdims=True)
        return jnp.min(jnp.where(d2 <= mn, iota[None, :], k),
                       axis=1).astype(jnp.int32)

    b, d = q.shape
    if b > chunk and b % chunk == 0:
        return jax.lax.map(tile_fn, q.reshape(-1, chunk, d)).reshape(-1)
    return tile_fn(q)


serve_assign_fused = jax.jit(_serve_fused_impl,
                             static_argnames=("chunk",))
# donated variant: the query buffer is dead after the labels are read,
# so accelerators may reuse it in place. No-op on CPU (jax warns), so
# make_serve_assign only routes here off-CPU.
serve_assign_fused_donated = jax.jit(_serve_fused_impl,
                                     static_argnames=("chunk",),
                                     donate_argnums=(0,))


@functools.partial(jax.jit, static_argnames=("core",))
def serve_assign_grouped(q, centroids, c2, groups, members, gsize, *,
                         core: PassCore):
    """Group-table batched assignment: the PassCore candidate pass with
    vacuous bounds (the same pass `assign` tiles), with centroids and
    group tables as runtime args so epoch swaps never recompile. The
    ``pallas`` backend routes to the ``grouped_assign`` block-skip
    kernel. Returns (B,) int32."""
    b = q.shape[0]
    x2 = row_norms_sq(q)
    a0 = jnp.zeros((b,), jnp.int32)
    ub = jnp.full((b,), jnp.inf, jnp.float32)
    lb = jnp.zeros((b, core.n_groups), jnp.float32)
    need = jnp.ones((b,), bool)
    nas, _, _, _, _ = core.candidate_pass(
        q, centroids, a0, ub, lb, need, groups, members, gsize,
        x2=x2, c2=c2)
    return nas


def make_serve_assign(snapshot_shape, *, backend: str = "fused",
                      chunk: int = 1024, interpret: bool | None = None,
                      donate: bool | None = None):
    """Resolve the serve-side batched assign callable for a centroid
    snapshot shape ``(k, n_groups)``.

    Returns ``fn(q, centroids, c2, groups, members, gsize) -> labels``
    — a uniform signature over all backends (the fused path ignores
    the tables). ``backend``: ``"fused"`` (dense GEMM + min-trick, the
    CPU winner), ``"grouped"`` (PassCore compact pass over the group
    tables), or ``"pallas"`` (the block-skip kernel; ``interpret=None``
    resolves through :func:`repro.platform.pallas_interpret`). All
    three are exact. ``donate`` (default: on except CPU,
    where donation is a no-op) donates the query buffer on the fused
    path — off-CPU this INVALIDATES a ``jax.Array`` the caller passes
    in ("Array has been deleted" on its next use), so only enable it
    for buffers the caller is done with; ``ServeEngine`` donates its
    own staging transfers and passes ``donate=False`` for client-owned
    device arrays on the exact-fit path."""
    k, n_groups = snapshot_shape
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if backend == "fused":
        fused = serve_assign_fused_donated if donate \
            else serve_assign_fused

        def run(q, centroids, c2, groups=None, members=None, gsize=None):
            return fused(q, centroids, c2, chunk=chunk)
        run.cache_size = fused._cache_size
        return run
    if backend not in ("grouped", "pallas"):
        raise ValueError(f"unknown serve backend {backend!r}")
    pc_backend = "pallas" if backend == "pallas" else "compact"
    if interpret is None:
        interpret = backend == "pallas" and pallas_interpret()

    def run(q, centroids, c2, groups, members, gsize):
        core = PassCore(backend=pc_backend, k=k, n_groups=n_groups,
                        cap_n=q.shape[0], cap_g=n_groups, chunk=chunk,
                        interpret=interpret)
        return serve_assign_grouped(q, centroids, c2, groups, members,
                                    gsize, core=core)
    run.cache_size = serve_assign_grouped._cache_size
    return run
