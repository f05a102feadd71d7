"""Serve-path knob family: the serving hot loop's tuned choices.

The serving subsystem (:mod:`repro.serve`) has its own configuration
axis, disjoint from :class:`~repro.core.engine.EngineConfig`: the
batched-assign backend and its internal tile, the micro-batching
bucket lattice, and the drift threshold at which the centroid index
rebuilds its group tables. The right values depend on (platform, K, D)
only — the serve path never sees a fixed N (batches are whatever the
queue coalesces), so N is not part of the signature.

Entries live in the same :class:`~repro.tune.cache.TuneCache` as the
engine's, under ``serve|``-prefixed signatures, so one cache file (and
one ``$REPRO_KMEANS_TUNE_CACHE`` override) covers both families.
Like engine tuning, serve tuning is pure wall-clock: every backend is
exact (``tests/test_serve.py`` asserts oracle parity), so a stale
cache can never corrupt labels.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from .cache import TuneCache, default_cache


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs of the serving hot loop (see ``docs/serving.md``).

    backend : batched-assign realisation — ``"fused"`` (dense GEMM +
        min-trick reduction; the CPU winner), ``"grouped"`` (PassCore
        compact pass over the group tables), ``"pallas"`` (block-skip
        kernel).
    chunk : `lax.map` tile inside one batch; keeps the per-tile
        (chunk, K) distance block cache-resident.
    max_batch : coalescing ceiling = largest padding bucket. Requests
        larger than this are split by ``ServeEngine.submit``.
    min_bucket : smallest padding bucket; ragged batches pad up to the
        next pow2 in [min_bucket, max_batch], so the compiled-program
        set is the bucket lattice, nothing else.
    max_wait_us : optional linger after the first request of a batch,
        trading p50 latency for batch fill (0 = serve greedily).
    rebuild_threshold : max cumulative per-centroid drift (relative to
        the typical centroid norm) the index tolerates before a publish
        rebuilds the group tables instead of reusing them. Reuse is
        always exact — stale grouping only costs pruning efficiency.
    """
    backend: str = "fused"
    chunk: int = 1024
    max_batch: int = 8192
    min_bucket: int = 256
    max_wait_us: int = 0
    rebuild_threshold: float = 0.05

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ServeConfig":
        """Tolerant inverse of :meth:`to_dict` (unknown keys from a
        newer writer are ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def replace(self, **kw) -> "ServeConfig":
        return dataclasses.replace(self, **kw)


DEFAULT_SERVE_CONFIG = ServeConfig()


def serve_signature(k: int, d: int, platform: str | None = None) -> str:
    """Cache key of the serve knob family — ``serve|platform|kK|dD``."""
    if platform is None:
        platform = jax.default_backend()
    return f"serve|{platform}|k{int(k)}|d{int(d)}"


def lookup_serve(*, k: int, d: int, platform: str | None = None,
                 cache: TuneCache | None = None) -> ServeConfig | None:
    """Tuned serve config for a (platform, K, D) signature, or None."""
    if cache is None:
        cache = default_cache()
    e = cache.entry(serve_signature(k, d, platform))
    if not e or "config" not in e:
        return None
    return ServeConfig.from_dict(e["config"])


def autotune_serve(*, k: int, d: int, backends=None,
                   chunks=(512, 1024, 2048), max_batch: int = 8192,
                   repeats: int = 5, cache: TuneCache | None = None,
                   store: bool = True) -> ServeConfig:
    """Measure the serve backend x chunk grid on a synthetic full
    bucket and persist the winner.

    Small by design: the serve grid is (backend, chunk) at ONE bucket
    shape — the bucket lattice itself is a shape policy, not a timing
    choice, and every candidate computes identical labels so best-of
    wall-clock is the whole objective.
    """
    from ..core import engine as _engine
    from ..core.distances import row_norms_sq

    if backends is None:
        backends = ["fused", "grouped"]
        if jax.default_backend() == "tpu":
            backends.append("pallas")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((max_batch, d)).astype(np.float32))
    centroids = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))
    c2 = row_norms_sq(centroids)
    groups, members, gsize = _engine.build_assign_tables(centroids)
    shape = (k, int(gsize.shape[0]))

    best_cfg, best_t = DEFAULT_SERVE_CONFIG, float("inf")
    for backend in backends:
        for chunk in chunks:
            fn = _engine.make_serve_assign(shape, backend=backend,
                                           chunk=int(chunk))
            jax.block_until_ready(      # warm-up; a backend error raises
                fn(q, centroids, c2, groups, members, gsize))
            t_best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    fn(q, centroids, c2, groups, members, gsize))
                t_best = min(t_best, time.perf_counter() - t0)
            if t_best < best_t:
                best_t = t_best
                best_cfg = ServeConfig(backend=backend, chunk=int(chunk),
                                       max_batch=int(max_batch))
    if store:
        if cache is None:
            cache = default_cache()
        cache.store(serve_signature(k, d), best_cfg,
                    points_per_sec=max_batch / max(best_t, 1e-12),
                    measured_ms=best_t * 1e3)
    return best_cfg
