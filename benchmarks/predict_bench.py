"""Predict-path benchmark: tiled PassCore assignment throughput.

``KMeans.predict`` no longer materialises an (N, K) distance matrix —
it runs the engine's tiled candidate pass with cached norms
(``engine.assign``). This module measures its throughput
(points/sec) on the uci-medium shape, checks exact parity with the
dense argmin, and records the row under the ``"predict"`` key of
``BENCH_kmeans.json`` so ``benchmarks/run.py --check`` can smoke it.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.kpynq import paper_suite
from repro.core import engine_fit, kmeans_plusplus
from repro.core import engine as _engine
from repro.data import make_points


def run(scale=1.0, dataset="uci-medium", repeats=5, tile_n=8192):
    prob = next(p for p in paper_suite if p.name == dataset)
    n = max(int(prob.n_points * scale), 2048)
    pts_np, _, _ = make_points(n, prob.n_dims, prob.k, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, prob.k)
    r = engine_fit(pts, init, n_groups=prob.n_groups, max_iters=20,
                   tol=prob.tol, backend="auto")

    def assign():
        labels, dists = _engine.assign(pts, r.centroids, tile_n=tile_n)
        jax.block_until_ready(labels)
        return labels, dists

    labels, dists = assign()                  # compile + warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        assign()
        best = min(best, time.perf_counter() - t0)

    # exactness: the tiled pass IS the dense argmin. Reference in the
    # SAME f32 norm-cached expression (pairwise_sq_dists) the engine
    # uses, so the gate is structural — an f64 numpy reference would
    # flip on sub-float-tolerance argmin margins and fail CI on a
    # correct assignment.
    from repro.core import pairwise_sq_dists
    ref = np.asarray(jnp.argmin(pairwise_sq_dists(pts, r.centroids),
                                axis=1))
    parity = bool(np.array_equal(np.asarray(labels), ref))
    return {
        "dataset": f"{dataset}-predict", "n": n, "d": prob.n_dims,
        "k": prob.k, "tile_n": tile_n,
        "predict_ms": best * 1e3,
        "points_per_sec": n / best,
        "labels_match_dense": parity,
    }


def write_json(row, path="BENCH_kmeans.json"):
    """Merge the predict record into the shared perf JSON."""
    payload = {}
    if os.path.exists(path):
        with open(path) as fh:
            payload = json.load(fh)
    payload["predict"] = row
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main(argv=None, *, scale=None, json_path=None):
    # CLI args used to be parsed by nobody: ``--scale 0.1 --out ""``
    # silently ran the full-scale bench AND overwrote the committed
    # BENCH row. Parse them for real (keyword args still win so tests
    # and run.py can call main() directly).
    if scale is None and json_path is None:
        ap = argparse.ArgumentParser()
        ap.add_argument("--scale", type=float, default=1.0)
        ap.add_argument("--out", default="BENCH_kmeans.json",
                        help="perf JSON to merge the predict row into "
                             "('' disables)")
        args = ap.parse_args(argv)
        scale, json_path = args.scale, args.out
    elif scale is None:
        scale = 1.0
    row = run(scale=scale)
    print("name,us_per_call,derived")
    print(f"predict/{row['dataset']},{row['predict_ms'] * 1e3:.1f},"
          f"pps={row['points_per_sec']:.0f} tile_n={row['tile_n']} "
          f"parity={'OK' if row['labels_match_dense'] else 'FAIL'}")
    if json_path:
        write_json(row, json_path)
    return row


if __name__ == "__main__":
    from repro.platform import use_compile_cache
    use_compile_cache()
    main()
