"""Distributed KPynq: data-parallel filtered K-means under shard_map.

Points are sharded along one (or a flattened set of) mesh axes; bounds
(ub/lb) and assignments live with their shard; centroids are replicated.
Each iteration the only communication is a psum of the (K, D) partial
sums + (K,) counts — exactly the FPGA design's "stream points through,
accumulate centroids centrally" pattern mapped onto ICI collectives
(and the simplified map-reduce framing of Li et al.: map = per-shard
assignment, reduce = the centroid psum). Filtering is per-shard local,
so the work saving composes with parallelism.

Both sharded fits are THIN WRAPPERS over the engine's pass core
(:func:`repro.core.engine.fit_core` — the one candidate-pass loop
implementation): this module contributes ONLY the ``shard_map`` specs,
the psum :class:`~repro.core.engine.Reducer`, and the host-side shard
padding. Exactness fixes in the core land in the local and distributed
paths at once; there is no distributed copy of the iteration.

Two per-shard realisations of the candidate pass:

``backend="compact"`` (default, :func:`make_fit_sharded_engine`)
    The engine's capacity-bucketed two-level compaction
    (``PassCore(backend="ladder")``): each shard carries its own bucket
    level through the ``lax.while_loop`` and switches levels
    shard-locally over a static capacity ladder (``engine.cap_ladders``
    / ``engine.select_bucket``) with the tuned downshift hysteresis —
    no host syncs anywhere in the sharded loop. The convergence test
    rides on the psum'd centroid sums (every shard sees the same
    drift, so the while conds agree), and the ``EvalCount`` work
    counter is psum'd at the end.
``backend="dense"`` (:func:`make_fit_sharded`)
    The masked-dense pass over every shard point
    (``PassCore(backend="oracle")``, exact, no skipped FLOPs) — the
    oracle the compact path is tested against, and the AOT-lowering
    target of the production-mesh dry-run.

Optional int8 compression of the psum payload (``compress=True``)
applies to the (K, D) partial-sums tensor only (counts, sample weights
and scalars stay exact) — the gradient-compression analogue for the
centroid sums, realised inside ``Reducer.sums``.

``sample_weight``: per-point weights shard with their points and enter
the psum'd sums/counts and the inertia through the core — every
reduction payload is weighted with the SAME single implementation as
the local fit.

Uneven shard sizes are handled by padding to the shard lattice with
sentinel rows (``assignment = K``, ``ub = 0``, ``lb = +inf``, weight 0
when weighted): the sentinel drops out of every ``segment_sum`` and the
zero/inf bounds keep padded rows filtered forever, so they cost no
candidate work and touch no statistics.

:func:`make_stream_bounds_sharded` / :func:`make_stream_update_sharded`
are the sharded instantiations of ``engine.stream_bounds`` /
``engine.stream_step`` — one global mini-batch split over the mesh,
candidate pass per shard, psum'd batch sums/counts feeding the decayed
EMA — driven by ``repro.streaming.StreamingKMeans(mesh=...)``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# check_vma=False: psum outputs are value-replicated but the static
# analysis cannot prove it through the while_loop carry.
_shard_map = functools.partial(jax.shard_map, check_vma=False)

from ..obs import ring as _obs_ring
from ..obs.metrics import normalize_obs
from . import engine as _engine
from .engine import (DEFAULT_CONFIG, EngineConfig, EngineStats, PassCore,
                     Reducer, StreamStepOut, build_group_tables,
                     cap_ladders, stream_bounds)
from .kmeans import KMeansResult, group_centroids


def make_fit_sharded(mesh: Mesh, axes, k: int, n_groups: int,
                     max_iters: int, tol: float, compress: bool = False,
                     opt_sq: bool = True, unroll_iters: int = 0,
                     weighted: bool = False, ring_iters: int = 0):
    """Build the jittable shard_map K-means fit with the masked-dense
    per-shard pass (AOT-lowerable for the production-mesh dry-run;
    executed by distributed_yinyang). The body is
    ``engine.fit_core(core=PassCore(backend="oracle", reducer=psum))``
    — no loop code lives here.

    ``opt_sq`` (default True, §Perf optimization): run the masked
    min/argmin pass on SQUARED distances (monotone, so results are
    identical) and sqrt only the reduced outputs. False exists for the
    dry-run's A/B cost analysis only — every driver runs True.

    ``weighted=True`` adds a per-point ``sample_weight`` argument,
    sharded with the points.

    unroll_iters>0: replace the while_loop with exactly that many python
    iterations of the SAME body — analysis artifacts only (XLA
    cost_analysis does not descend into while bodies; the N-vs-(N-1)
    unrolled diff gives the exact per-iteration cost).

    ``ring_iters>0`` carries the per-iteration telemetry ring through
    the loop (``repro.obs.ring``); the sixth output is the PER-SHARD
    ring stack (S, ring_iters, C), pre-reduction — join with
    ``obs.ring.reduce_shard_rings``."""
    axes = tuple(axes)
    pspec = P(axes, None)
    core = PassCore(backend="oracle", k=k, n_groups=n_groups,
                    opt_sq=opt_sq, ring_iters=ring_iters,
                    reducer=Reducer(axes=axes, compress=compress))
    out_specs = (P(None, None), P(axes), P(), P(), P(),
                 P(axes, None, None))

    in_specs = (pspec, P(None, None)) + ((P(axes),) if weighted else ())

    @functools.partial(_shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def fit_sharded(local_points, init_c, *rest):
        weights = rest[0] if weighted else None
        groups = group_centroids(init_c, n_groups)
        dummy_members = jnp.full((n_groups, 1), -1, jnp.int32)
        dummy_gsize = jnp.zeros((n_groups,), jnp.float32)
        if unroll_iters > 0:
            out = _engine.fit_core_unrolled(
                local_points, init_c, groups, dummy_members, dummy_gsize,
                core=core, n_iters=unroll_iters, weights=weights)
        else:
            out = _engine.fit_core(
                local_points, init_c, groups, dummy_members, dummy_gsize,
                core=core, max_iters=max_iters, tol=tol, weights=weights)
        # ring stays shard-local: add the leading shard axis the
        # out_spec concatenates over
        return out[:5] + (out[5][None],)

    return fit_sharded


def make_fit_sharded_engine(mesh: Mesh, axes, k: int, n_groups: int,
                            max_iters: int, tol: float, *, shard_n: int,
                            compress: bool = False,
                            config: EngineConfig | None = None,
                            max_branches: int = 12,
                            weighted: bool = False, ring_iters: int = 0):
    """Build the compact (capacity-bucketed) sharded fit.

    Returns a shard_map'd ``fit(local_points, valid[, weights], init_c,
    groups, members, gsize) -> (centroids, assignments, n_iters, evals,
    inertia, shard_rings)`` where ``valid`` masks sentinel padding rows
    (see module
    docstring), ``groups`` is the (K,) centroid->group map and
    ``members``/``gsize`` the host-built group tables
    (``engine.build_group_tables`` — built OUTSIDE the sharded program,
    so the per-point group buckets use the true ``Lmax``, not the K
    upper bound).

    The body is ``engine.fit_core`` at a ``PassCore(backend="ladder",
    reducer=psum)``: the engine's split-loop construction with the
    bucket machinery fully in-trace — each shard carries
    ``(level_n, level_g)`` through the while_loop and transitions via
    ``engine.select_bucket`` using its OWN candidate count / group
    high-water — per-shard work-proportional capacities with zero host
    round trips. ``cfg.min_cap`` floors the ladder;
    ``cfg.down_n``/``down_g`` set the downshift hysteresis;
    ``cfg.chunk`` and ``cfg.group_gather_factor`` pick each branch's
    gather-vs-GEMM crossover; ``cfg.refresh_in_pass`` places the
    own-distance refresh (full-shard rowwise vs on the compacted
    survivor buffer).

    ``ring_iters>0`` enables the per-iteration telemetry ring; the
    sixth output stacks the PER-SHARD rings (S, ring_iters, C) —
    shard-local candidate counts / evals / ladder levels, the raw
    material for the straggler watchdog and skew gauges.
    """
    axes = tuple(axes)
    cfg = config or DEFAULT_CONFIG
    cap_ns, cap_gs = cap_ladders(shard_n, n_groups, min_cap=cfg.min_cap,
                                 max_branches=max_branches)
    core = PassCore.from_config(
        cfg, backend="ladder", k=k, n_groups=n_groups,
        reducer=Reducer(axes=axes, compress=compress),
        cap_ns=cap_ns, cap_gs=cap_gs, ring_iters=ring_iters)
    pspec = P(axes, None)
    out_specs = (P(None, None), P(axes), P(), P(), P(),
                 P(axes, None, None))

    in_specs = (pspec, P(axes)) + ((P(axes),) if weighted else ()) + \
        (P(None, None), P(None), P(None, None), P(None))

    @functools.partial(_shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def fit_sharded(local_points, valid, *rest):
        weights, rest = (rest[0], rest[1:]) if weighted else (None, rest)
        init_c, groups, members, gsize = rest
        out = _engine.fit_core(
            local_points, init_c, groups, members, gsize, core=core,
            max_iters=max_iters, tol=tol, weights=weights, valid=valid)
        return out[:5] + (out[5][None],)

    return fit_sharded


def _mesh_shards(mesh: Mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))


def make_mesh(shards: int, axis: str = "data", devices=None) -> Mesh:
    """A 1-D data mesh over the first ``shards`` devices — the helper
    every driver (and the elastic restart path, which rebuilds a mesh
    of a DIFFERENT size around one checkpoint) uses instead of
    hand-rolling ``Mesh(np.array(jax.devices()[:n]), ...)``."""
    devices = list(jax.devices() if devices is None else devices)
    if shards > len(devices):
        raise ValueError(
            f"requested {shards} shards but only {len(devices)} "
            f"devices are available")
    return Mesh(np.array(devices[:shards]), (axis,))


# Builder memos: a fresh shard_map closure is a fresh jit cache key, so
# without these every distributed_yinyang call would re-trace AND
# re-compile the whole sharded program (the compact ladder compiles one
# pass instance per bucket level — seconds of XLA time on CPU).
@functools.lru_cache(maxsize=64)
def _jitted_fit_dense(mesh: Mesh, axes, k, n_groups, max_iters, tol,
                      compress, weighted, ring_iters=0):
    return jax.jit(make_fit_sharded(mesh, axes, k, n_groups, max_iters,
                                    tol, compress, weighted=weighted,
                                    ring_iters=ring_iters))


@functools.lru_cache(maxsize=64)
def _jitted_fit_engine(mesh: Mesh, axes, k, n_groups, max_iters, tol,
                       shard_n, compress, config, max_branches, weighted,
                       ring_iters=0):
    return jax.jit(make_fit_sharded_engine(
        mesh, axes, k, n_groups, max_iters, tol, shard_n=shard_n,
        compress=compress, config=config, max_branches=max_branches,
        weighted=weighted, ring_iters=ring_iters))


def _pad_sharded(arr_np: np.ndarray, shards: int):
    """Pad (N, ...) to a multiple of ``shards`` rows; returns
    ``(padded, valid bool mask)``."""
    n = len(arr_np)
    n_pad = (-n) % shards
    valid = np.arange(n + n_pad) < n
    if n_pad:
        pad = np.zeros((n_pad,) + arr_np.shape[1:], arr_np.dtype)
        arr_np = np.concatenate([arr_np, pad], axis=0)
    return arr_np, valid


def _sharded_stats(backend, shard_rings, n_iters, *, n, k, cfg, obs_cfg,
                   watchdog) -> EngineStats:
    """Build the serializable :class:`EngineStats` of one sharded fit
    from its drained per-shard rings; feed the straggler watchdog and
    publish the skew gauge when configured. Host python on fetched
    values — runs only under ``return_stats``/``obs``."""
    shard_rings = np.asarray(jax.device_get(shard_rings))
    shard_rings = shard_rings[:, :n_iters + 1]            # trim to fit
    ring = _obs_ring.reduce_shard_rings(shard_rings)
    skew = _obs_ring.shard_skew(shard_rings)
    stats = EngineStats(
        backend=backend, n_iters=n_iters, host_syncs=1, n_points=n,
        config=cfg.to_dict() if cfg is not None else {},
        ring=ring, init_evals=float(n) * k, shard_rings=shard_rings,
        shard_skew=skew, caps_history=_obs_ring.caps_from_ring(ring))
    per_shard_work = shard_rings[:, :, _obs_ring.COL_EVALS]    # (S, R)
    if watchdog is not None:
        for t in range(per_shard_work.shape[1]):
            watchdog.observe_shards(t, per_shard_work[:, t])
    if obs_cfg is not None:
        reg = obs_cfg.resolve_registry()
        labels = {"backend": backend}
        hist = reg.histogram("dist_shard_skew",
                             "per-iteration max/mean work skew",
                             labels=labels,
                             buckets=(1.0, 1.1, 1.25, 1.5, 2.0, 4.0, 8.0))
        for s in skew:
            hist.observe(float(s))
        reg.gauge("dist_last_shard_skew", "final-iteration work skew",
                  labels=labels).set(float(skew[-1]) if len(skew) else 1.0)
        reg.gauge("dist_last_n_iters", "iterations of the last sharded "
                  "fit", labels=labels).set(float(n_iters))
        reg.log_event("distributed_fit", backend=backend,
                      n_iters=n_iters, n_points=n,
                      shards=int(shard_rings.shape[0]),
                      telemetry=stats.telemetry())
    return stats


def distributed_yinyang(points, init_centroids, mesh: Mesh,
                        axes: Sequence[str] = ("data",),
                        n_groups: int | None = None,
                        max_iters: int = 100, tol: float = 1e-4,
                        compress: bool = False, backend: str = "compact",
                        config: EngineConfig | None = None,
                        tune: str = "auto",
                        max_branches: int = 12,
                        sample_weight=None, return_stats: bool = False,
                        obs=None, watchdog=None):
    """Run filtered K-means with points sharded over ``axes`` of ``mesh``.

    ``backend="compact"`` (default) runs the engine's two-level
    capacity-bucketed compaction per shard (see
    :func:`make_fit_sharded_engine`); ``"dense"`` keeps the masked-dense
    per-shard pass (exact oracle; requires N divisible by the shard
    count). Both are instantiations of the SAME
    :func:`repro.core.engine.fit_core`. ``tune`` consults the
    per-(platform, N, K, D, shards) tuning cache for the compact body's
    capacities/crossovers (``"force"`` runs the measured sharded search
    on a miss — see :func:`repro.tune.autotune` ``shards=``);
    ``config`` pins them explicitly.

    ``sample_weight``: optional (N,) per-point weights, sharded with
    their points (weighted psum'd sums/counts + weighted inertia; the
    int8 ``compress`` payload stays the (K, D) sums only).

    ``points`` may be a host array (it is sharded — and, on the compact
    path, padded to the shard lattice — on entry) or an already-sharded
    jax.Array with the right layout.

    ``return_stats=True`` returns ``(result, EngineStats)`` with the
    drained telemetry: the reduced per-iteration ring, the raw
    per-shard ``shard_rings`` and the per-iteration ``shard_skew``
    (max/mean work imbalance — the straggler signal under lockstep
    SPMD). ``obs`` additionally publishes skew gauges and a
    ``distributed_fit`` event to the metrics registry
    (:mod:`repro.obs`); ``watchdog`` feeds each iteration's per-shard
    work into a :class:`repro.runtime.StragglerWatchdog` via
    ``observe_shards``. Enabling any of these changes dispatch only —
    results stay bit-identical.
    """
    if backend not in ("compact", "dense"):
        raise ValueError(f"unknown distributed backend {backend!r}; "
                         f"expected 'compact' or 'dense'")
    if tune not in ("auto", "off", "force"):
        raise ValueError(f"unknown tune mode {tune!r}; expected "
                         f"'auto', 'off' or 'force'")
    k = init_centroids.shape[0]
    if n_groups is None:
        n_groups = max(k // 10, 1)
    n_groups = int(min(n_groups, k))
    axes = tuple(axes)
    shards = _mesh_shards(mesh, axes)
    init_c = jnp.asarray(init_centroids, jnp.float32)
    weighted = sample_weight is not None
    w_np = None if sample_weight is None else \
        np.asarray(jax.device_get(sample_weight), np.float32)
    obs_cfg = normalize_obs(obs)
    want_stats = return_stats or obs_cfg is not None or \
        watchdog is not None
    ring_iters = int(max_iters) + 1 if want_stats else 0

    shard = NamedSharding(mesh, P(axes, None))
    shard1 = NamedSharding(mesh, P(axes))
    repl = NamedSharding(mesh, P())

    if backend == "dense":
        n = points.shape[0]
        if n % shards:
            raise ValueError(
                f"backend='dense' needs N ({n}) divisible by the shard "
                f"count ({shards}); use backend='compact' for uneven "
                f"shards")
        fit_sharded = _jitted_fit_dense(mesh, axes, k, n_groups,
                                        int(max_iters), float(tol),
                                        bool(compress), weighted,
                                        ring_iters)
        points = jax.device_put(points, shard)
        init_d = jax.device_put(init_c, repl)
        args = (points, init_d)
        if weighted:
            args = (points, init_d,
                    jax.device_put(jnp.asarray(w_np), shard1))
        c, a, i, evals, inertia, rings = fit_sharded(*args)
        result = KMeansResult(c, a, i, evals, inertia)
        if not want_stats:
            return result
        stats = _sharded_stats("dense", rings, int(i), n=n, k=k,
                               cfg=config, obs_cfg=obs_cfg,
                               watchdog=watchdog)
        return (result, stats) if return_stats else result

    n, d = points.shape
    if n % shards:
        # uneven: materialise on host once to append the sentinel rows
        pts_in, valid_np = _pad_sharded(
            np.asarray(jax.device_get(points), np.float32), shards)
        if weighted:
            w_np, _ = _pad_sharded(w_np, shards)   # pad rows: weight 0
    else:
        # no padding needed: device-resident arrays stay on device
        # (jnp.asarray is a no-op for committed f32 arrays)
        pts_in = jnp.asarray(points, jnp.float32)
        valid_np = np.ones((n,), bool)
    shard_n = len(pts_in) // shards
    cfg = _resolve_sharded_config(
        points, init_c, mesh, axes, shard_n=shard_n, k=k, d=d,
        shards=shards, config=config, tune=tune, n_groups=n_groups,
        max_iters=int(max_iters), tol=float(tol))

    # group map + tables, built once on the host (true Lmax)
    groups = group_centroids(init_c, n_groups)
    groups_np = np.asarray(jax.device_get(groups))
    members, gsize = build_group_tables(groups_np, n_groups)

    fit_sharded = _jitted_fit_engine(
        mesh, axes, k, n_groups, int(max_iters), float(tol), shard_n,
        bool(compress), cfg, int(max_branches), weighted, ring_iters)
    args = [jax.device_put(pts_in, shard),
            jax.device_put(valid_np, shard1)]
    if weighted:
        args.append(jax.device_put(jnp.asarray(w_np), shard1))
    args += [jax.device_put(init_c, repl),
             jax.device_put(groups, repl),
             jax.device_put(members, repl),
             jax.device_put(gsize, repl)]
    c, a, i, evals, inertia, rings = fit_sharded(*args)
    if len(pts_in) != n:
        # drop the sentinel rows; replicated first, the slice is
        # unambiguous on meshes of either axis type (Auto or Explicit)
        a = jax.device_put(a, repl)[:n]
    result = KMeansResult(c, a, i, evals, inertia)
    if not want_stats:
        return result
    stats = _sharded_stats("compact", rings, int(i), n=n, k=k, cfg=cfg,
                           obs_cfg=obs_cfg, watchdog=watchdog)
    return (result, stats) if return_stats else result


def _resolve_sharded_config(points, init_c, mesh, axes, *, shard_n, k, d,
                            shards, config, tune, n_groups, max_iters,
                            tol) -> EngineConfig:
    """Config precedence for the compact sharded fit: explicit
    ``config`` > tuned ``...|sS`` cache entry > (``tune="force"`` only)
    a fresh measured sharded search over THIS mesh > the single-device
    entry for the per-shard shape > defaults."""
    if config is not None:
        return config
    if tune == "off":
        return DEFAULT_CONFIG
    from .. import tune as _tune
    cfg = _tune.lookup(n=shard_n, k=k, d=d, shards=shards)
    if cfg is None and tune == "force":
        cfg = _tune.autotune(
            jnp.asarray(points, jnp.float32)[:shard_n], init_c,
            n_groups=n_groups, max_iters=max_iters, tol=tol,
            shards=shards, mesh=mesh, axes=axes)
    if cfg is None:
        cfg = _tune.lookup(n=shard_n, k=k, d=d)
    return cfg or DEFAULT_CONFIG


# --------------------------------------------------------------------------
# sharded streaming steps (driven by repro.streaming.StreamingKMeans)
# --------------------------------------------------------------------------

def make_stream_bounds_sharded(mesh: Mesh, axes: Sequence[str] = ("data",)):
    """Sharded analogue of ``engine.stream_bounds``: the point-level
    filter over carried (drift-inflated) bounds, per shard of one
    global mini-batch. Returns a jitted ``(points, centroids, assign,
    ub, lb) -> (ub_t, need, max_shard_cand, tightened)`` where
    ``max_shard_cand`` is the pmax'd PER-SHARD candidate count — the
    number the caller's static ``cap_n`` must cover."""
    axes = tuple(axes)

    @functools.partial(
        _shard_map, mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(axes), P(axes),
                  P(axes, None)),
        out_specs=(P(axes), P(axes), P(), P()),
    )
    def bounds(points, centroids, assign, ub, lb):
        ub_t, need, n_cand, n_tight = stream_bounds(points, centroids,
                                                    assign, ub, lb)
        return (ub_t, need, jax.lax.pmax(n_cand, axes),
                jax.lax.psum(n_tight, axes))

    return jax.jit(bounds)


def make_stream_update_sharded(mesh: Mesh, axes, *, k: int, n_groups: int,
                               cap_n: int, cap_g: int, chunk: int = 2048,
                               group_gather_factor: int = 4,
                               compress: bool = False,
                               weighted: bool = False):
    """Sharded instantiation of ``engine.stream_step``: one global
    mini-batch split over the mesh, the SAME step body per shard with a
    psum :class:`~repro.core.engine.Reducer` — the reduced batch
    sums/counts make the decayed EMA (and drift) replicated, and the
    scalar telemetry is psum'd/pmax'd by the reducer inside the step.
    ``cap_n`` must cover the max PER-SHARD candidate count (the caller
    syncs it via :func:`make_stream_bounds_sharded`). Returns a jitted
    function with the :class:`~repro.core.engine.StreamStepOut` result;
    ``assignments``/``ub``/``lb`` come back sharded along ``axes``
    (gathered to the global batch on read). ``compress=True``
    int8-compresses the (K, D) partial-sums psum payload only.
    ``weighted=True`` adds a sharded per-point ``weights`` argument."""
    axes = tuple(axes)
    core = PassCore(backend="compact", k=k, n_groups=n_groups,
                    cap_n=cap_n, cap_g=cap_g, chunk=chunk,
                    group_gather_factor=group_gather_factor,
                    reducer=Reducer(axes=axes, compress=compress))
    out_specs = StreamStepOut(
        P(None, None), P(None), P(axes), P(axes), P(axes, None),
        P(), P(), P(None), P(None), P(None), P())
    base_specs = (P(axes, None), P(None, None), P(None), P(), P(None),
                  P(None, None), P(None), P(axes), P(axes), P(axes, None),
                  P(axes))

    in_specs = base_specs + ((P(axes),) if weighted else ())

    @functools.partial(_shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    def update(points, centroids, counts, decay, groups, members,
               gsize, assignments, ub_t, lb, need, *rest):
        weights = rest[0] if weighted else None
        return _engine.stream_step(
            points, centroids, counts, decay, groups, members, gsize,
            assignments, ub_t, lb, need, weights, core=core)

    return jax.jit(update)
