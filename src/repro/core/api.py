"""Public sklearn-flavoured API for the KPynq K-means family."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import compile_count, span
from . import engine as _engine
from . import kmeans as _km
from .init import kmeans_plusplus, random_init


class NotFittedError(ValueError, AttributeError):
    """Raised when results are requested from an unfitted estimator.

    Inherits both ValueError and AttributeError (the sklearn
    convention) so existing ``except AttributeError`` call sites keep
    working while the message actually says what went wrong.
    """


class KMeans:
    """Exact K-means with KPynq's multi-level triangle-inequality filters.

    Parameters
    ----------
    n_clusters : K
    algorithm : 'lloyd' | 'hamerly' | 'yinyang'
        'hamerly' = the paper's point-level filter alone (one group);
        'yinyang' = point-level + group-level filters (the full KPynq
        multi-level filter).
    n_groups : group count for 'yinyang' (default K//10, the paper-family
        heuristic).
    init : 'k-means++' | 'random'
    engine : None | 'auto' | 'oracle' | 'compact' | 'pallas' | 'lloyd'
        None runs the reference ``lax.while_loop`` implementation in
        :mod:`repro.core.kmeans`. Any other value routes the filtered
        algorithms through the device-resident execution engine
        (:mod:`repro.core.engine`), which realises both filter levels
        as skipped work — 'auto' picks the Pallas block-skip kernel on
        TPU and two-level stream compaction elsewhere, EXCEPT tiny
        problems (``n * k <= engine.AUTO_LLOYD_MAX_WORK``), which it
        routes straight to the dense Lloyd loop (measurably faster
        there; same fixed point). Results are identical either way;
        only the wall-clock changes. Ignored for ``algorithm='lloyd'``
        (there is nothing to filter).
    tune : 'auto' | 'off' | 'force'
        Per-(platform, N, K, D) autotuning of the engine configuration
        (:mod:`repro.tune`; cache at ``~/.cache/repro_kmeans_tune.json``
        unless ``REPRO_KMEANS_TUNE_CACHE`` overrides). 'auto' (default)
        uses a cached winner when one exists; 'force' runs the measured
        search on a cache miss (one-time cost, persisted; the STREAMING
        path never measures — there 'force' degrades to 'auto'); 'off'
        uses the engine's built-in defaults. Tuning changes wall-clock
        only — results are bit-identical. Only consulted when
        ``engine`` is not None.
    decay : per-batch count decay for the STREAMING path (see
        :meth:`partial_fit`); unused by :meth:`fit`.
    obs : observability switch (see :mod:`repro.obs`): ``None``/``False``
        off, ``True`` defaults, a ``MetricsRegistry``/``ObsConfig`` for
        control. Engine-path fits record the per-iteration telemetry
        ring into ``stats_`` and publish metrics/events to the
        registry; the streaming path publishes per-batch throughput /
        drift / cache metrics. Results are bit-identical with obs on
        or off.

    After an engine-path :meth:`fit`, ``stats_`` holds the
    :class:`repro.core.engine.EngineStats` (telemetry ring included
    when ``obs`` is enabled; ``compiles`` counts the programs the whole
    call lowered, seeding included); ``None`` otherwise.

    :meth:`fit` opens host spans (:func:`repro.obs.span`) that a
    recording profiler places on the device ops' clock: ``kpynq.fit``
    around the call, ``kpynq.seed`` around the seeding and
    ``kpynq.fetch`` around the result's transfer to the host, with the
    engine's ``kpynq.tables``, ``kpynq.loop`` and ``kpynq.epilogue``
    between them.
    """

    def __init__(self, n_clusters: int, algorithm: str = "yinyang",
                 n_groups: int | None = None, init: str = "k-means++",
                 max_iters: int = 100, tol: float = 1e-4, seed: int = 0,
                 engine: str | None = None, decay: float = 1.0,
                 tune: str = "auto", obs=None):
        if algorithm not in ("lloyd", "hamerly", "yinyang"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        if engine is not None and engine not in ("auto", "lloyd") \
                and engine not in _engine.BACKENDS:
            raise ValueError(
                f"unknown engine {engine!r}; expected None, 'auto', "
                f"'lloyd' or one of {_engine.BACKENDS}")
        if tune not in ("auto", "off", "force"):
            raise ValueError(f"unknown tune mode {tune!r}; expected "
                             f"'auto', 'off' or 'force'")
        self.n_clusters = n_clusters
        self.algorithm = algorithm
        self.n_groups = n_groups
        self.init = init
        self.max_iters = max_iters
        self.tol = tol
        self.seed = seed
        self.engine = engine
        self.decay = decay
        self.tune = tune
        self.obs = obs
        self.stats_: _engine.EngineStats | None = None
        self.result_: _km.KMeansResult | None = None
        self._stream = None
        self._assign_tables = None  # cached (groups, members, gsize, g)

    def _init_centroids(self, points, weights=None):
        key = jax.random.PRNGKey(self.seed)
        if self.init == "k-means++":
            return kmeans_plusplus(key, points, self.n_clusters,
                                   weights=weights)
        return random_init(key, points, self.n_clusters)

    def fit(self, points, sample_weight=None) -> "KMeans":
        """Batch fit. ``sample_weight``: optional (N,) per-point
        weights — weighted centroid means and inertia through every
        backend, AND weighted D^2 sampling in the k-means++ seeding (a
        weight-m point seeds like m duplicates); the filters are
        weight-independent, so the work saving is unchanged. ``None``
        is bit-identical to uniform weights of 1.0 for the fit and
        runs the seed's original seeding program."""
        with span("kpynq.fit"):
            compiles0 = compile_count()
            points = jnp.asarray(points)
            weights = None if sample_weight is None else \
                jnp.asarray(sample_weight, jnp.float32)
            with span("kpynq.seed"):
                init_c = self._init_centroids(points, weights)
            self.stats_ = None    # only engine-path fits produce stats
            if self.algorithm == "lloyd":
                res = _km.lloyd(points, init_c, self.max_iters, self.tol,
                                weights=weights)
            else:
                n_groups = 1 if self.algorithm == "hamerly" \
                    else self.n_groups
                if self.engine is None:
                    res = _km.yinyang(points, init_c, n_groups=n_groups,
                                      max_iters=self.max_iters, tol=self.tol,
                                      weights=weights)
                else:
                    res, self.stats_ = _engine.fit(
                        points, init_c, n_groups=n_groups,
                        max_iters=self.max_iters, tol=self.tol,
                        backend=self.engine, tune=self.tune,
                        sample_weight=weights, obs=self.obs,
                        return_stats=True, compiles_since=compiles0)
            with span("kpynq.fetch"):
                self.result_ = jax.tree.map(jax.device_get, res)
        self._stream = None       # a batch fit supersedes any stream state
        self._assign_tables = None
        return self

    def partial_fit(self, points, shard_id=None,
                    sample_weight=None) -> "KMeans":
        """Streaming mini-batch update (delegates to
        :class:`repro.streaming.StreamingKMeans`).

        Feed point shards one at a time; each batch runs the engine's
        two-level-filtered candidate pass against the current centroids
        and applies a decayed count-weighted (EMA) centroid update.
        ``shard_id`` (any hashable) keys the carried-bounds cache: pass
        it when the same points will be re-presented (e.g. epochs over
        a :class:`repro.data.PointStream`), so triangle-inequality
        bounds survive across batches and skip most distance work on
        revisits.

        Decay schedule: effective per-centroid counts are multiplied by
        ``self.decay`` before each update. ``decay=1.0`` is pure
        count-weighting (per-centroid 1/n learning rate — converges to
        the batch fit on stationary streams); ``decay<1`` forgets with
        a ~``1/(1-decay)``-batch horizon (for drifting streams).

        The first call(s) may only BUFFER points (k-means++ cold-start
        over the first shards); accessors raise ``NotFittedError``
        until enough points arrived. Afterwards ``cluster_centers_``
        etc. track the running stream state; ``inertia_`` is the EWA
        per-point batch cost (an upper-bound estimate), not full-data
        inertia, and ``n_iter_`` counts batches.
        """
        from .. import streaming as _streaming
        if self._stream is None:
            n_groups = 1 if self.algorithm in ("lloyd", "hamerly") \
                else self.n_groups
            self._stream = _streaming.StreamingKMeans(
                self.n_clusters, n_groups=n_groups, init=self.init,
                decay=self.decay, seed=self.seed, tune=self.tune,
                obs=self.obs)
        s = self._stream.partial_fit(points, shard_id=shard_id,
                                     sample_weight=sample_weight)
        if s.initialized:
            self.result_ = _km.KMeansResult(
                s.cluster_centers_, s.labels_,
                np.int32(s.stats_.batches),
                np.float32(s.stats_.distance_evals),
                np.float32(s.ewa_inertia_))
            self._assign_tables = None    # centroids moved this batch
        return self

    def _fitted(self) -> _km.KMeansResult:
        if self.result_ is None:
            raise NotFittedError(
                f"This KMeans instance is not fitted yet; call "
                f"fit() before using this "
                f"{type(self).__name__} attribute/method.")
        return self.result_

    # sklearn-style accessors ------------------------------------------------
    @property
    def cluster_centers_(self):
        return self._fitted().centroids

    @property
    def labels_(self):
        return self._fitted().assignments

    @property
    def inertia_(self):
        return float(self._fitted().inertia)

    @property
    def n_iter_(self):
        return int(self._fitted().n_iters)

    @property
    def distance_evals_(self):
        """Work-efficiency counter: distance evaluations performed."""
        return float(self._fitted().distance_evals)

    # inference ---------------------------------------------------------------

    def _tables(self):
        """Group tables over the FITTED centroids, built once and
        reused by every predict/score call (invalidated by fit /
        partial_fit)."""
        if self._assign_tables is None:
            centroids = jnp.asarray(self._fitted().centroids, jnp.float32)
            g = self.n_groups if self.algorithm == "yinyang" else 1
            groups, members, gsize = _engine.build_assign_tables(
                centroids, g)
            self._assign_tables = (centroids, groups, members, gsize)
        return self._assign_tables

    def _assign(self, points):
        centroids, groups, members, gsize = self._tables()
        return _engine.assign(points, centroids, groups=groups,
                              members=members, gsize=gsize)

    def predict(self, points):
        """Tiled exact nearest-centroid assignment through the PassCore
        candidate pass (``engine.assign``): norm-cached, no O(N*K)
        distance buffer at large N."""
        labels, _ = self._assign(points)
        return jax.device_get(labels)

    def fit_predict(self, points, sample_weight=None):
        """Fit, then return the training assignments (sklearn parity:
        equivalent to ``fit(X).labels_`` but one call)."""
        return self.fit(points, sample_weight=sample_weight).labels_

    def transform(self, points):
        """Distances of ``points`` to every fitted centroid, (N, K) —
        sklearn's cluster-distance space. The output is O(N*K) by
        definition, but it is computed TILED with cached norms, so the
        working set beyond the result stays bounded."""
        from .distances import pairwise_dists, row_norms_sq
        centroids = jnp.asarray(self._fitted().centroids, jnp.float32)
        pts = jnp.asarray(points)
        if pts.dtype != jnp.float32:
            pts = pts.astype(jnp.float32)
        c2 = row_norms_sq(centroids)
        tile = 8192
        out = [pairwise_dists(pts[lo:lo + tile], centroids, None, c2)
               for lo in range(0, pts.shape[0], tile)]
        return jax.device_get(jnp.concatenate(out, axis=0))

    def score(self, points, sample_weight=None):
        """Negative (weighted) inertia of ``points`` under the fitted
        centroids — the sklearn convention (greater is better)."""
        _, dists = self._assign(points)
        d2 = dists * dists
        if sample_weight is not None:
            d2 = d2 * jnp.asarray(sample_weight, jnp.float32)
        return -float(jnp.sum(d2))
