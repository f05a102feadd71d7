"""The paper's workload end-to-end: UCI-like suite + distributed run.

  PYTHONPATH=src python examples/kmeans_clustering.py [--scale 0.25]

Runs the KPynq algorithm (multi-level filter), the point-level-only
variant, the stream-compaction execution mode, the STREAMING mini-batch
fit (bound-carrying ``partial_fit`` over deterministic shards — the
never-in-memory-at-once path), and — on a multi-device runtime — the
shard_map data-parallel version, reporting work reduction for each
(the paper's Table, reproduced at whatever scale fits the machine).
Also demos the observability layer: an engine fit with the telemetry
ring on, printing the per-iteration filter-efficiency table (see
``docs/observability.md``).

Streaming decay schedule: ``StreamingKMeans(decay=1.0)`` (used here) is
pure count-weighting — per-centroid 1/n learning rates, converging to
the batch fit on stationary data; ``decay<1`` forgets with a
~1/(1-decay)-batch horizon for drifting streams.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs.kpynq import paper_suite
from repro.core import (distributed_yinyang, engine_fit, kmeans_plusplus,
                        lloyd, yinyang)
from repro.data import PointStream, make_points
from repro.obs import ObsConfig, format_ring_table
from repro.streaming import StreamingKMeans


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--max-datasets", type=int, default=4)
    args = ap.parse_args()

    print(f"{'dataset':12s} {'N':>9s} {'D':>4s} {'K':>5s} "
          f"{'iters':>5s} {'work_red':>9s} {'hamerly':>8s}")
    for prob in paper_suite[:args.max_datasets]:
        n = max(int(prob.n_points * args.scale), 1024)
        pts_np, _, _ = make_points(n, prob.n_dims, prob.k, seed=0)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, prob.k)
        r_l = lloyd(pts, init, prob.max_iters, prob.tol)
        r_y = yinyang(pts, init, max_iters=prob.max_iters, tol=prob.tol)
        r_h = yinyang(pts, init, n_groups=1, max_iters=prob.max_iters,
                      tol=prob.tol)
        wr = float(r_l.distance_evals) / float(r_y.distance_evals)
        wh = float(r_l.distance_evals) / float(r_h.distance_evals)
        print(f"{prob.name:12s} {n:9d} {prob.n_dims:4d} {prob.k:5d} "
              f"{int(r_y.n_iters):5d} {wr:8.1f}x {wh:7.1f}x")

    # compaction mode: the engine's ``compact`` backend, the whole fit
    # in one device-resident loop, with the telemetry ring on — the
    # device records per-iteration filter efficiency (candidates
    # surviving, evals spent, active capacity bucket, drift) with ZERO
    # extra host syncs, drained once at exit. Results are bit-identical
    # with the ring on or off.
    pts_np, _, _ = make_points(32768, 32, 256, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 256)
    r_c, stats = engine_fit(pts, init, max_iters=40, backend="compact",
                            tune="off", return_stats=True, obs=ObsConfig())
    print(f"\ncompaction mode: iters={int(r_c.n_iters)} "
          f"evals={float(r_c.distance_evals):.3g} "
          f"inertia={float(r_c.inertia):.1f}")
    print("\nper-iteration filter efficiency (telemetry ring):")
    print(format_ring_table(stats.ring, stats.n_points, max_rows=12))
    print(f"telemetry: {stats.telemetry()}")

    # streaming / mini-batch: the SAME dataset as the compaction demo,
    # fed as 2048-point shards through partial_fit. Epochs 2+ revisit
    # shards, so the per-shard triangle-inequality bounds (inflated by
    # accumulated centroid drift) skip most of the distance work —
    # watch cache_hits and the work reduction vs a dense mini-batch
    # pass.
    stream = PointStream(shard_size=2048, data=pts_np)
    skm = StreamingKMeans(256, seed=1, init_size=4096)
    skm.fit_stream(stream, epochs=3)
    st = skm.stats_
    gap = skm.inertia_of(pts_np) / float(r_c.inertia) - 1.0
    print(f"streaming fit: batches={st.batches} "
          f"cache_hits={st.cache_hits} reseeds={st.reseeds} "
          f"work_red={st.points_seen * 256 / max(st.distance_evals, 1):.1f}x "
          f"inertia gap vs batch: {gap * 100:+.2f}%")

    # weighted clustering: sample_weight threads through every backend
    # and driver via the one PassCore implementation — uniform weights
    # are bit-identical to the unweighted fit, and integer weights are
    # exactly equivalent to duplicating points (cheaper by the weight
    # mass). Demo: upweight the first blob 5x and watch its centroid
    # mass grow without touching the filter work.
    import numpy as np
    from repro.core import KMeans
    km = KMeans(n_clusters=8, engine="auto", seed=1)
    sub = np.asarray(pts_np[:8192])
    w = np.where(np.arange(len(sub)) < 1024, 5.0, 1.0).astype(np.float32)
    km.fit(sub, sample_weight=w)
    print(f"weighted fit: inertia={km.inertia_:.1f} "
          f"score(training)={km.score(sub, sample_weight=w):.1f}")

    # the predict path: tiled PassCore assignment — no (N, K) distance
    # matrix, norm-cached, exact. transform() gives the sklearn
    # cluster-distance space (tiled too), fit_predict the one-call fit.
    labels = km.predict(sub)
    print(f"predict (tiled): {len(labels)} labels, "
          f"first tile matches transform argmin: "
          f"{bool((labels[:100] == km.transform(sub[:100]).argmin(1)).all())}")

    # distributed (shard_map) — uses however many devices exist
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    r_d = distributed_yinyang(pts, init, mesh, max_iters=40)
    print(f"distributed ({n_dev} devices): inertia={float(r_d.inertia):.1f} "
          f"matches single-device: "
          f"{abs(float(r_d.inertia) - float(r_c.inertia)) / float(r_c.inertia) < 1e-4}")


if __name__ == "__main__":
    main()
