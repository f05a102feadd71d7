"""Measured configuration search: a hill-climb / grid hybrid.

The engine's knobs interact too much for closed-form choice (AccD's
core observation: distance-kernel configuration must be *searched* per
shape, not hand-picked), but the space is small and benign enough that
exhaustive grid search is waste. The hybrid here:

1. **Backend grid** — measure one default-knob candidate per viable
   backend (``lloyd`` / ``compact`` / ``pallas`` on TPU). The dense
   Lloyd GEMM is always in the running: for filter-hostile shapes
   (tiny N*K, or K so large the group filter never bites) *not
   filtering* is the fastest correct engine, and making that a
   first-class tuning outcome is what keeps ``mean_speedup >= 1``
   honest.
2. **Coordinate hill-climb** — from the winning backend, sweep each of
   its knobs over a small lattice, adopting strict improvements, for
   up to ``max_rounds`` rounds (stop early when a round finds
   nothing). Deterministic given a deterministic ``measure``.

Measurements go through an injectable ``measure(config) -> seconds``
so tests can drive the search with a stub; the default measures real
wall-clock (best-of-``repeats`` of a full ``engine.fit``, compile
excluded by a warmup call).

Correctness is never at stake: every candidate produces bit-identical
assignments/inertia (``tests/test_tune.py`` asserts it), so the search
can be aggressive and its cache can be stale, wrong-platform, or
hand-edited without risking results.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from ..core.engine import EngineConfig
from ..obs.metrics import default_registry
from ..obs.trace import span
from .cache import TuneCache, default_cache
from .signature import signature

# knob -> candidate lattice. Kept small on purpose: each point is a
# compile + a few timed fits.
KNOB_LATTICE = {
    "min_cap": (128, 256, 512, 1024),
    "chunk": (1024, 2048, 4096),
    "group_gather_factor": (2, 4, 8),
    "down_n": (0, 2, 4),
    "down_g": (0, 2, 4, 8),
    "refresh_in_pass": (False, True),
    "tile_n": (128, 256, 512),
}

# which knobs matter per backend (lloyd has none: its only knob IS
# being lloyd). refresh_in_pass first: it changes the capacity regime
# the other knobs are then refined under.
BACKEND_KNOBS = {
    "compact": ("refresh_in_pass", "min_cap", "chunk",
                "group_gather_factor", "down_n", "down_g"),
    "pallas": ("tile_n", "min_cap"),
    "oracle": (),
    "lloyd": (),
}


def candidate_backends(platform: str) -> tuple:
    if platform == "tpu":
        return ("pallas", "compact", "lloyd")
    return ("compact", "lloyd")


def _best_of(run, repeats):
    """Best-of-``repeats`` wall-clock of ``run`` (warmup excluded);
    sub-ms runs keep sampling until ~50ms of timing has accumulated
    (capped) so one noisy sample cannot flip a backend decision."""
    run()                                        # compile + warm caches
    best = float("inf")
    done = 0
    spent = 0.0
    while done < repeats or (spent < 0.05 and done < 4 * repeats):
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        best = min(best, dt)
        spent += dt
        done += 1
    return best


def timing_measure(points, init_c, *, n_groups=None, max_iters=50,
                   tol=1e-4, repeats=3):
    """Default measurement: best-of-``repeats`` wall-clock of a full
    ``engine.fit`` under the candidate config (warmup excluded)."""
    from ..core import engine

    def measure(cfg: EngineConfig) -> float:
        def run():
            r = engine.fit(points, init_c, n_groups=n_groups,
                           max_iters=max_iters, tol=tol, config=cfg,
                           tune="off")
            jax.block_until_ready(jax.tree.leaves(r))
        return _best_of(run, repeats)

    return measure


def sharded_timing_measure(shard_points, init_c, shards: int, *,
                           mesh=None, axes=("data",), n_groups=None,
                           max_iters=50, tol=1e-4, repeats=3):
    """Measurement hook for the DISTRIBUTED signatures (``...|sS``):
    best-of-``repeats`` wall-clock of ``distributed_yinyang(backend=
    "compact", config=cfg)`` — the unified driver under ``shard_map``
    — so sharded winners are produced by sharded measurement, not the
    single-device fallback.

    ``shard_points`` is ONE SHARD's worth of points (the unit the
    ``...|sS`` signature is keyed on); the global problem is its
    ``shards``-fold tiling, which keeps the per-shard shapes (and thus
    the compiled programs) exactly those of a real S-way fit.
    ``mesh=None`` builds a 1-D mesh over the first ``shards`` local
    devices (raises when the runtime has fewer — force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=S`` on CPU).
    """
    import numpy as np

    from ..core.distributed import distributed_yinyang

    if mesh is None:
        devs = jax.devices()
        if len(devs) < shards:
            raise ValueError(
                f"sharded_timing_measure needs >= {shards} devices, "
                f"found {len(devs)}; on CPU force them with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{shards}")
        mesh = jax.sharding.Mesh(np.asarray(devs[:shards]), ("data",))
        axes = ("data",)
    axes = tuple(axes)
    global_pts = jnp.concatenate([jnp.asarray(shard_points)] * shards,
                                 axis=0)

    def measure(cfg: EngineConfig) -> float:
        def run():
            r = distributed_yinyang(
                global_pts, init_c, mesh, axes=axes, n_groups=n_groups,
                max_iters=max_iters, tol=tol, backend="compact",
                config=cfg, tune="off")
            jax.block_until_ready(jax.tree.leaves(r))
        return _best_of(run, repeats)

    return measure


def autotune(points, init_c, *, n_groups=None, max_iters: int = 50,
             tol: float = 1e-4, cache: TuneCache | None = None,
             measure=None, repeats: int = 3, max_rounds: int = 2,
             max_measurements: int = 32, platform: str | None = None,
             shards: int = 1, mesh=None, axes=("data",),
             verbose: bool = False) -> EngineConfig:
    """Search the engine configuration space for this problem and
    persist the winner under its (platform, N, K, D[, shards])
    signature.

    Returns the winning :class:`EngineConfig`. ``measure`` overrides
    the wall-clock measurement (tests use a stub); ``max_measurements``
    bounds the total number of distinct configs measured.

    ``shards > 1`` tunes the DISTRIBUTED key (``points`` then being one
    shard's worth): the default measure is
    :func:`sharded_timing_measure` — the unified driver under
    ``shard_map`` over ``mesh`` (built from the first ``shards`` local
    devices when None), so ``...|sS`` winners come from sharded
    measurement. The backend grid is skipped there (the sharded body
    realises its own compact pass; Lloyd is not a sharded candidate)
    and the climb runs over the compact knobs.
    """
    if platform is None:
        platform = jax.default_backend()
    n, d = points.shape
    k = init_c.shape[0]
    sig = signature(n, k, d, platform, shards=shards)
    if cache is None:
        cache = default_cache()
    if measure is None:
        if shards > 1:
            measure = sharded_timing_measure(
                points, init_c, shards, mesh=mesh, axes=axes,
                n_groups=n_groups, max_iters=max_iters, tol=tol,
                repeats=repeats)
        else:
            measure = timing_measure(points, init_c, n_groups=n_groups,
                                     max_iters=max_iters, tol=tol,
                                     repeats=repeats)

    memo: dict = {}

    def cost(cfg: EngineConfig) -> float:
        key = tuple(sorted(cfg.to_dict().items()))
        if key not in memo:
            if len(memo) >= max_measurements:
                return float("inf")
            with span("tune.measure", registry=default_registry(),
                      sig=sig, backend=cfg.backend) as fields:
                memo[key] = float(measure(cfg))
                fields["best_s"] = memo[key]
            if verbose:
                print(f"tune[{sig}] {cfg.backend} "
                      f"{memo[key] * 1e3:8.2f}ms  {cfg.to_dict()}")
        return memo[key]

    # phase 1: backend grid at default knobs. Lloyd is the bar to
    # clear, not a climb candidate (it has no knobs) — so climb the
    # best FILTERED backend even when the default-knob seed loses to
    # Lloyd, and only settle the backend question after the climb.
    # (Deciding at seed stage threw away configs that beat Lloyd only
    # after tuning — exactly the medium-shape regime this issue is
    # about.) Sharded keys have no backend question: the shard_map body
    # is always the ladder'd compact pass, so only its knobs climb.
    if shards > 1:
        lloyd_cost = None
        best = EngineConfig(backend="compact")
        best_cost = cost(best)
        climb_knobs = BACKEND_KNOBS["compact"]
    else:
        lloyd_cost = cost(EngineConfig(backend="lloyd"))
        engine_seeds = [EngineConfig(backend=b)
                        for b in candidate_backends(platform)
                        if b != "lloyd"]
        best = min(engine_seeds, key=cost)
        best_cost = cost(best)
        climb_knobs = BACKEND_KNOBS[best.backend]

    # phase 2: coordinate hill-climb over the filtered winner's knobs
    for _ in range(max_rounds):
        improved = False
        for knob in climb_knobs:
            for val in KNOB_LATTICE[knob]:
                if val == getattr(best, knob):
                    continue
                cand = best.replace(**{knob: val})
                c = cost(cand)
                if c < best_cost:
                    best, best_cost = cand, c
                    improved = True
        if not improved:
            break

    # phase 3: the backend decision, made on tuned-vs-lloyd terms
    if lloyd_cost is not None and lloyd_cost < best_cost:
        best, best_cost = EngineConfig(backend="lloyd"), lloyd_cost

    extra = {} if lloyd_cost is None else {"lloyd_ms": lloyd_cost * 1e3}
    cache.store(sig, best, ms=best_cost * 1e3, measured=len(memo),
                n=int(n), k=int(k), d=int(d), shards=int(shards),
                **extra)
    if verbose:
        vs = "" if lloyd_cost is None else \
            f" vs lloyd {lloyd_cost * 1e3:.2f}ms"
        print(f"tune[{sig}] winner: {best.backend} "
              f"{best_cost * 1e3:.2f}ms{vs} ({len(memo)} configs)")
    return best


def get_or_tune(points, init_c, *, n_groups=None, max_iters: int = 50,
                tol: float = 1e-4, cache: TuneCache | None = None,
                **tune_kw) -> EngineConfig:
    """Cached-or-searched config for this problem (``fit(tune='force')``
    lands here): return the cache hit if present, else run
    :func:`autotune` and return (and persist) the winner."""
    if cache is None:
        cache = default_cache()
    n, d = points.shape
    k = init_c.shape[0]
    hit = cache.lookup(signature(n, k, d))
    if hit is not None:
        return hit
    return autotune(points, init_c, n_groups=n_groups,
                    max_iters=max_iters, tol=tol, cache=cache, **tune_kw)
