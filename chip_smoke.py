"""Smoke run of the three user paths — fit, serve, stream — on the TPU.

    python chip_smoke.py               # one chip: fit, serve, stream
    python chip_smoke.py --chips 4     # four chips: sharded fit + stream
    python chip_smoke.py --rehearse [--chips 4]   # the CPU, small sizes

Size: ``uci-xlarge`` from ``repro.configs.kpynq`` (N=1,048,576, D=32,
K=256, G=25), data from ``repro.data.make_points(seed=...)``.

* fit — ``KMeans(engine="auto")`` (the Pallas block-skip kernel,
  compiled) and ``KMeans(engine="compact")``, each against a plain
  float32 Lloyd written here and run at ``highest`` matmul precision
  from the same k-means++ centroids: inertia within 1e-4 relative and
  at least 99.9% of the labels equal.
* serve — the fitted centroids published to a ``CentroidIndex`` and
  served by ``ServeEngine`` (default ``fused`` backend, then
  ``pallas``): ~200 ragged requests of 64–4,096 points plus one
  request larger than ``max_batch``. Every label is the nearest
  centroid by exact per-coordinate distances, or ties with it within
  1e-5 relative.
* stream — ``StreamingKMeans.partial_fit`` over 8 batches of 131,072
  points from ``PointStream``; the inertia gap to the batch fit is
  reported, the predicted labels are checked like the served ones.
* ``--chips 4`` runs only the sharded path: ``distributed_yinyang``
  (``compact`` and ``dense``) over ``make_mesh(4)`` against the
  single-chip engine fit (compact and dense labels identical, inertia
  within 1e-4 relative of the single chip), and a sharded
  ``StreamingKMeans(mesh=...)`` against the single-chip stream.

Every line names the device. Timings (second, warm call, waited on with
``block_until_ready``) and peak device memory are information, not
checks. A failed check raises, so the exit code is non-zero and the
last line is not printed; the last line of a passing run is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Without a TPU the script exits non-zero, except under ``--rehearse``,
which runs every phase on the CPU at a small size (the Pallas kernel in
interpret mode) and names the CPU in its last line: a rehearsal of the
control flow, never a chip result.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
STREAM_BATCHES = 8
SERVE_REQUESTS = 200


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Log:
    """Prints every line prefixed with the device it ran on."""

    def __init__(self, devices):
        d = devices[0]
        self.device = {"platform": d.platform, "kind": d.device_kind,
                       "count": len(devices)}
        self.tag = f"[{d.platform} {d.device_kind} x{len(devices)}]"
        self.devices = devices

    def __call__(self, msg: str) -> None:
        print(f"{self.tag} {msg}", flush=True)

    def memory(self, phase: str) -> None:
        stats = self.devices[0].memory_stats()
        peak = None if stats is None else stats.get("peak_bytes_in_use")
        self(f"{phase}: peak_bytes_in_use={peak}")


def _timed(fn):
    """(result, seconds) of ``fn()`` waited on with block_until_ready."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# -- references written here, independent of the code under test ----------

@functools.partial(jax.jit, static_argnames=("max_iters", "tol"))
def reference_lloyd(x, c0, *, max_iters: int, tol: float):
    """Plain float32 Lloyd at ``highest`` matmul precision: assign by
    argmin, segment-sum means (empty clusters keep their centroid), stop
    when no centroid moves more than ``tol`` or after ``max_iters``.
    Returns (centroids, labels of the last assignment, iterations,
    inertia of those labels against the final centroids)."""
    hi = jax.lax.Precision.HIGHEST
    k = c0.shape[0]
    x2 = jnp.sum(x * x, axis=1)

    def body(state):
        i, c, _, _ = state
        d2 = (x2[:, None] - 2.0 * jnp.dot(x, c.T, precision=hi)
              + jnp.sum(c * c, axis=1)[None, :])
        a = jnp.argmin(d2, axis=1).astype(jnp.int32)
        sums = jax.ops.segment_sum(x, a, num_segments=k)
        cnt = jax.ops.segment_sum(jnp.ones_like(x2), a, num_segments=k)
        new = jnp.where(cnt[:, None] > 0,
                        sums / jnp.maximum(cnt, 1.0)[:, None], c)
        shift = jnp.max(jnp.sqrt(jnp.sum((new - c) ** 2, axis=1)))
        return i + 1, new, a, shift

    def cond(state):
        i, _, _, shift = state
        return jnp.logical_and(i < max_iters, shift > tol)

    init = (jnp.int32(0), c0, jnp.zeros(x.shape[0], jnp.int32),
            jnp.float32(jnp.inf))
    i, c, a, _ = jax.lax.while_loop(cond, body, init)
    inertia = jnp.sum((x - c[a]) ** 2)
    return c, a, i, inertia


@functools.partial(jax.jit, static_argnames=("chunk",))
def _label_distances(q, labels, c, *, chunk: int):
    """Per query: squared distance to its given label, the exact minimum
    over all centroids and its argmin — per-coordinate differences, no
    matmul, so no matmul precision enters the oracle."""
    n, d = q.shape
    pad = (-n) % chunk
    qp = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, chunk, d)
    lp = jnp.pad(labels, (0, pad)).reshape(-1, chunk)

    def tile(args):
        qt, lt = args
        d2 = jnp.sum((qt[:, None, :] - c[None, :, :]) ** 2, axis=-1)
        own = jnp.take_along_axis(d2, lt[:, None], axis=1)[:, 0]
        return own, jnp.min(d2, axis=1), jnp.argmin(d2, axis=1)

    own, dmin, arg = jax.lax.map(tile, (qp, lp))
    return (own.reshape(-1)[:n], dmin.reshape(-1)[:n],
            arg.reshape(-1)[:n].astype(jnp.int32))


def nearest_violations(q, labels, centroids) -> tuple[int, int]:
    """(labels that are not the nearest centroid and do not tie with it
    within 1e-5 relative distance, labels that differ from the argmin
    only by such a tie)."""
    labels = np.asarray(labels, np.int32)
    check(labels.shape == (len(q),), f"labels shape {labels.shape}")
    check(labels.min() >= 0 and labels.max() < len(centroids),
          "label out of range")
    own, dmin, arg = _label_distances(
        jnp.asarray(q), jnp.asarray(labels), jnp.asarray(centroids),
        chunk=512)
    own, dmin, arg = (np.asarray(own), np.asarray(dmin), np.asarray(arg))
    tie = np.sqrt(own) <= np.sqrt(dmin) * (1.0 + 1e-5)
    differ = labels != arg
    return int(np.sum(differ & ~tie)), int(np.sum(differ & tie))


# -- phases ------------------------------------------------------------------

def fit_phase(log, pts, prob, *, seed: int, pallas_engine: str):
    """Batch fits through ``KMeans`` against the reference Lloyd."""
    from repro.core import KMeans, kmeans_plusplus

    k, n = prob.k, pts.shape[0]
    # the centroids KMeans(seed=seed) seeds itself with
    init = kmeans_plusplus(jax.random.PRNGKey(seed), pts, k)
    with jax.default_matmul_precision("highest"):
        _, ref_a, ref_i, ref_inertia = reference_lloyd(
            pts, init, max_iters=prob.max_iters, tol=prob.tol)
        (_, ref_a, ref_i, ref_inertia), t_ref = _timed(
            lambda: reference_lloyd(pts, init, max_iters=prob.max_iters,
                                    tol=prob.tol))
    ref_a = np.asarray(ref_a)
    ref_inertia = float(ref_inertia)
    log(f"fit reference lloyd (highest precision): iters={int(ref_i)} "
        f"inertia={ref_inertia!r} warm_s={t_ref!r}")

    fitted = None
    for engine in (pallas_engine, "compact"):
        def run():
            return KMeans(n_clusters=k, algorithm="yinyang",
                          n_groups=prob.n_groups, engine=engine,
                          tune="off", max_iters=prob.max_iters,
                          tol=prob.tol, seed=seed).fit(pts)
        run()                                       # compile
        km, dt = _timed(run)                        # fit() ends on the host
        st = km.stats_
        want = "pallas" if engine == pallas_engine else "compact"
        check(st is not None and st.backend == want,
              f"engine={engine!r} ran backend "
              f"{getattr(st, 'backend', None)!r}, expected {want!r}")
        if want == "pallas":
            check(st.interpret == (log.device["platform"] == "cpu"),
                  f"pallas kernel interpret={st.interpret} on "
                  f"{log.device['platform']}")
        labels = np.asarray(km.labels_)
        mism = int(np.sum(labels != ref_a))
        rel = abs(km.inertia_ - ref_inertia) / ref_inertia
        log(f"fit engine={engine} backend={st.backend} "
            f"interpret={st.interpret} iters={km.n_iter_} "
            f"inertia={km.inertia_!r} inertia_rel_err={rel!r} "
            f"label_mismatches={mism}/{n} "
            f"distance_evals={km.distance_evals_!r} warm_s={dt!r}")
        check(np.all(np.isfinite(km.cluster_centers_)),
              f"{engine}: non-finite centroids")
        check(rel <= 1e-4, f"{engine}: inertia rel err {rel} > 1e-4")
        check(mism <= 0.001 * n,
              f"{engine}: {mism} label mismatches > 0.1% of {n}")
        if fitted is None:
            fitted = km
    log.memory("fit")
    return fitted


def serve_phase(log, pts_np, centroids, n_groups: int, rng):
    """Ragged and jumbo requests through ``ServeEngine``, per backend."""
    from repro.serve import CentroidIndex, ServeEngine
    from repro.tune import DEFAULT_SERVE_CONFIG, ServeConfig

    n = len(pts_np)
    sizes = rng.integers(64, 4097, size=SERVE_REQUESTS)
    starts = rng.integers(0, n - 4096, size=SERVE_REQUESTS)
    reqs = [pts_np[s:s + m] for s, m in zip(starts, sizes)]
    index = CentroidIndex(centroids, n_groups=n_groups)
    for backend in ("fused", "pallas"):
        config = None if backend == "fused" else ServeConfig(backend="pallas")
        max_batch = (config or DEFAULT_SERVE_CONFIG).max_batch
        with ServeEngine(index, config=config, tune="off") as eng:
            jumbo = pts_np[:3 * max_batch + 123]
            batch = reqs + [jumbo]

            def run():
                futs = [eng.submit(r) for r in batch]
                return [f.result() for f in futs]
            run()                                   # compile the buckets
            results, dt = _timed(run)
        q = np.concatenate(batch)
        labels = np.concatenate([r.labels for r in results])
        bad, ties = nearest_violations(q, labels, centroids)
        log(f"serve backend={backend} requests={len(batch)} "
            f"points={len(q)} jumbo={len(jumbo)} max_batch={max_batch} "
            f"batches={eng.batches} wrong={bad} exact_ties={ties} "
            f"warm_s={dt!r} points_per_s={len(q) / dt!r}")
        check(len(jumbo) > max_batch, "jumbo request is not larger than "
              "max_batch")
        check(all(r.epoch == results[0].epoch for r in results),
              "responses from more than one epoch")
        check(bad == 0, f"serve {backend}: {bad} labels are not the "
              f"nearest centroid")
    log.memory("serve")


def stream_phase(log, pts_np, k: int, n_groups: int, batch_inertia: float,
                 *, seed: int, mesh=None):
    """``StreamingKMeans.partial_fit`` over the stream's first batches;
    returns the estimator."""
    from repro.data import PointStream
    from repro.streaming import StreamingKMeans

    b = len(pts_np) // STREAM_BATCHES
    stream = PointStream(b, data=pts_np)

    def run():
        skm = StreamingKMeans(k, n_groups=n_groups, tune="off", seed=seed,
                              mesh=mesh)
        for sid, batch in stream.batches(epochs=1):
            skm.partial_fit(batch, shard_id=sid)
        return skm.cluster_centers_, skm
    run()                                           # compile
    (centers, skm), dt = _timed(run)
    check(centers.shape == (k, pts_np.shape[1]), f"centers {centers.shape}")
    check(np.all(np.isfinite(centers)), "non-finite stream centroids")
    check(skm.stats_.batches == STREAM_BATCHES,
          f"stream saw {skm.stats_.batches} batches")
    inertia = skm.inertia_of(pts_np)
    gap = inertia / batch_inertia - 1.0
    last = stream.shard(STREAM_BATCHES - 1)
    bad, ties = nearest_violations(last, skm.predict(last), centers)
    where = "sharded" if mesh is not None else "single"
    log(f"stream {where} batches={STREAM_BATCHES}x{b} "
        f"inertia={inertia!r} gap_vs_batch_fit={gap!r} "
        f"distance_evals={skm.stats_.distance_evals!r} "
        f"predict_wrong={bad} exact_ties={ties} warm_s={dt!r} "
        f"points_per_s={len(pts_np) / dt!r}")
    check(np.isfinite(inertia), "non-finite stream inertia")
    check(bad == 0, f"stream predict: {bad} labels are not the nearest "
          f"centroid")
    log.memory(f"stream {where}")
    return skm


def sharded_phase(log, pts_np, prob, *, seed: int, chips: int):
    """The four-chip path: sharded fits and a sharded stream, against
    the same work on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import (distributed_yinyang, engine_fit,
                            kmeans_plusplus, make_mesh)

    k, n = prob.k, len(pts_np)
    mesh = make_mesh(chips)
    devices = list(mesh.devices.flat)

    def in_use():
        return [d.memory_stats()["bytes_in_use"]
                if d.memory_stats() else None for d in devices]
    before = in_use()
    pts = jax.device_put(pts_np, NamedSharding(mesh, P("data", None)))
    after = in_use()
    log(f"sharded points {pts.shape} over {chips} devices: "
        f"bytes_in_use before={before} after={after}")
    check(len(pts.sharding.device_set) == chips, "points not split")
    single_pts = jax.device_put(pts_np, devices[0])
    init = kmeans_plusplus(jax.random.PRNGKey(seed), single_pts, k)
    kw = dict(n_groups=prob.n_groups, max_iters=prob.max_iters,
              tol=prob.tol, tune="off")
    engine_fit(single_pts, init, **kw)
    (single, st), dt = _timed(lambda: engine_fit(single_pts, init,
                                                 return_stats=True, **kw))
    s_inertia = float(single.inertia)
    log(f"single-chip engine fit backend={st.backend} "
        f"iters={int(single.n_iters)} inertia={s_inertia!r} warm_s={dt!r}")
    labels = {}
    for backend in ("compact", "dense"):
        distributed_yinyang(pts, init, mesh, backend=backend, **kw)
        r, dt = _timed(lambda: distributed_yinyang(pts, init, mesh,
                                                   backend=backend, **kw))
        labels[backend] = np.asarray(r.assignments)
        rel = abs(float(r.inertia) - s_inertia) / s_inertia
        mism = int(np.sum(labels[backend] != np.asarray(single.assignments)))
        log(f"sharded fit backend={backend} iters={int(r.n_iters)} "
            f"inertia={float(r.inertia)!r} inertia_rel_err={rel!r} "
            f"label_mismatches_vs_single={mism}/{n} warm_s={dt!r}")
        check(rel <= 1e-4, f"sharded {backend}: inertia rel err {rel}")
    diff = int(np.sum(labels["compact"] != labels["dense"]))
    log(f"sharded compact vs dense: label_differences={diff}")
    check(diff == 0, f"sharded compact and dense differ on {diff} labels")
    log.memory("sharded fit")

    sk_single = stream_phase(log, pts_np, k, prob.n_groups, s_inertia,
                             seed=seed)
    sk_mesh = stream_phase(log, pts_np, k, prob.n_groups, s_inertia,
                           seed=seed, mesh=mesh)
    check(sk_mesh.stats_.sharded_batches == sk_mesh.stats_.batches > 0,
          "the sharded stream did not run the sharded step")
    i_single = sk_single.inertia_of(pts_np)
    i_mesh = sk_mesh.inertia_of(pts_np)
    rel = abs(i_mesh - i_single) / i_single
    mass = (float(sk_mesh.counts_.sum()), float(sk_single.counts_.sum()))
    log(f"sharded vs single stream: inertia_rel_diff={rel!r} "
        f"mass={mass}")
    check(mass[0] == mass[1], f"stream mass differs {mass}")
    check(rel <= 1e-4, f"sharded stream inertia differs by {rel}")


# -- entry point -------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fit, serve and stream on one chip; 4: only "
                         "the sharded path over four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a small size (never a chip "
                         "result)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.platform import use_compile_cache
    use_compile_cache()

    devices = jax.devices()
    platform = devices[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"chip_smoke: JAX found {platform!r} devices, not a TPU "
              f"(--rehearse runs on the CPU)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    log = Log(devices[:args.chips] if args.chips > 1 else devices)

    from repro.configs.kpynq import paper_suite
    from repro.data import make_points

    prob = next(p for p in paper_suite if p.name == "uci-xlarge")
    if args.rehearse:
        prob = dataclasses.replace(prob, name=prob.name + "-rehearsal",
                                   n_points=16_384, k=64, max_iters=20)
    if prob.n_groups is None:
        prob = dataclasses.replace(prob, n_groups=prob.k // 10)
    t0 = time.perf_counter()
    pts_np, _, _ = make_points(prob.n_points, prob.n_dims, prob.k,
                               seed=args.seed)
    log(f"data {prob.name}: N={prob.n_points} D={prob.n_dims} K={prob.k} "
        f"G={prob.n_groups} max_iters={prob.max_iters} tol={prob.tol} "
        f"seed={args.seed} make_s={time.perf_counter() - t0!r} "
        f"jax={jax.__version__}")

    if args.chips > 1:
        sharded_phase(log, pts_np, prob, seed=args.seed, chips=args.chips)
    else:
        pts = jax.device_put(pts_np)
        # the TPU's default engine; the CPU's auto routes to compact,
        # so the rehearsal names the kernel to run it (interpreted)
        km = fit_phase(log, pts, prob, seed=args.seed,
                       pallas_engine="pallas" if args.rehearse else "auto")
        rng = np.random.default_rng(args.seed + 1)
        serve_phase(log, pts_np, np.asarray(km.cluster_centers_),
                    prob.n_groups, rng)
        stream_phase(log, pts_np, prob.k, prob.n_groups, km.inertia_,
                     seed=args.seed)
    print(json.dumps({"ok": True, "device": log.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
