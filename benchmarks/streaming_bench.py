"""Streaming mini-batch K-means: throughput + inertia gap vs batch fit.

The ROADMAP north-star workload: points arrive as shards, the fit never
holds the dataset at once. Reports, on the uci-medium config:

* ``cold_pps`` — points/sec of the first pass (cache-miss path: every
  batch pays the full candidate pass + JIT warmup);
* ``warm_pps`` — points/sec of subsequent epochs, where the per-shard
  carried bounds (drift-inflated across batches) skip most work;
* ``inertia_gap`` — final-inertia-vs-full-batch-engine gap (the
  acceptance metric: must stay within 5%);
* work/cache diagnostics from ``StreamStats``.

Merged into BENCH_kmeans.json under the ``"streaming"`` key so the
``benchmarks/run.py --check`` gate covers the subsystem.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs.kpynq import paper_suite
from repro.core import engine_fit, kmeans_plusplus
from repro.data import PointStream, make_points
from repro.streaming import StreamingKMeans


def run(scale=1.0, epochs=3, shard_size=2048, dataset="uci-medium"):
    prob = next(p for p in paper_suite if p.name == dataset)
    n = max(int(prob.n_points * scale), 2048)
    pts_np, _, _ = make_points(n, prob.n_dims, prob.k, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, prob.k)

    t0 = time.perf_counter()
    r_b = engine_fit(pts, init, n_groups=prob.n_groups,
                     max_iters=prob.max_iters, tol=prob.tol, backend="auto")
    jax.block_until_ready(r_b.centroids)
    t_batch = time.perf_counter() - t0

    stream = PointStream(shard_size=min(shard_size, n), data=pts_np)
    skm = StreamingKMeans(prob.k, n_groups=prob.n_groups, seed=1,
                          init_size=min(2 * shard_size, n))
    t0 = time.perf_counter()
    skm.fit_stream(stream, epochs=1)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    if epochs > 1:
        skm.fit_stream(stream, epochs=epochs - 1)
    t_warm = max(time.perf_counter() - t0, 1e-9)

    inertia_stream = skm.inertia_of(pts_np)
    st = skm.stats_
    return {
        "dataset": f"{dataset}-stream", "n": n, "d": prob.n_dims,
        "k": prob.k, "shard_size": stream.shard_size, "epochs": epochs,
        "batches": st.batches,
        "cold_pps": n / t_cold,
        "warm_pps": (max(epochs - 1, 0) * n) / t_warm if epochs > 1
        else n / t_cold,
        "batch_ms": t_batch * 1e3,
        "stream_ms": (t_cold + (t_warm if epochs > 1 else 0.0)) * 1e3,
        "inertia_batch": float(r_b.inertia),
        "inertia_stream": inertia_stream,
        "inertia_gap": inertia_stream / max(float(r_b.inertia), 1e-12) - 1.0,
        "distance_evals": st.distance_evals,
        "dense_equiv_evals": float(st.points_seen) * prob.k,
        "cache_hits": st.cache_hits, "cache_misses": st.cache_misses,
        "drift_resets": st.drift_resets, "reseeds": st.reseeds,
    }


def write_json(row, path="BENCH_kmeans.json"):
    """Merge the streaming record into the shared perf JSON."""
    payload = {}
    if os.path.exists(path):
        with open(path) as fh:
            payload = json.load(fh)
    payload["streaming"] = row
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main(scale=1.0, epochs=3, json_path=None):
    row = run(scale=scale, epochs=epochs)
    print("name,us_per_call,derived")
    print(f"streaming/{row['dataset']},{row['stream_ms'] * 1e3:.1f},"
          f"warm_pps={row['warm_pps']:.0f} cold_pps={row['cold_pps']:.0f} "
          f"inertia_gap={row['inertia_gap'] * 100:+.2f}% "
          f"work_red={row['dense_equiv_evals'] / max(row['distance_evals'], 1):.2f}x "
          f"hits={row['cache_hits']}/{row['batches']} "
          f"resets={row['drift_resets']} reseeds={row['reseeds']}")
    if json_path:
        write_json(row, json_path)
    return row


if __name__ == "__main__":
    from repro.platform import use_compile_cache
    use_compile_cache()
    main(json_path="BENCH_kmeans.json")
