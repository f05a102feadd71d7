"""Serve-path benchmark: batched throughput + Poisson open-loop latency.

Measures the ``repro.serve`` subsystem end to end — queue, coalescing,
bucket padding, epoch swap, batched assign — in the two regimes that
matter for a live index:

* **saturation throughput**: closed-loop bulk requests (vector
  quantization / bulk re-labelling traffic) keep the engine's batch
  pipeline full; points/s is the headline that the ISSUE's >=8x-over-
  single-stream-predict criterion gates (``run.py --check``);
* **open-loop latency**: Poisson arrivals of small ragged query blocks
  at a fraction of saturation, with a CONCURRENT centroid publisher
  refreshing the index mid-load — p50/p99 per-request latency, epoch
  swaps observed by responses, and exact per-epoch oracle parity on
  sampled responses.

Writes the ``"serve"`` row of ``BENCH_kmeans.json``; ``--check`` gates
parity + the p99 ceiling (the CI serve lane) and exports the latency
histogram JSONL artifact.

  PYTHONPATH=src python -m benchmarks.serve_bench --scale 0.1 --check
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.kpynq import paper_suite
from repro.core import engine_fit, kmeans_plusplus, pairwise_sq_dists
from repro.data import make_points
from repro.obs import MetricsRegistry
from repro.serve import CentroidIndex, ServeEngine
from repro.tune import ServeConfig, lookup_serve


def _fit_centroids(prob, n):
    pts_np, _, _ = make_points(n, prob.n_dims, prob.k, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, prob.k)
    r = engine_fit(pts, init, n_groups=prob.n_groups, max_iters=20,
                   tol=prob.tol, backend="auto")
    out = np.asarray(r.centroids)
    # drop the fit's live buffers and compiled programs so the serve
    # phases measure a clean steady state, not allocator fragmentation
    del r, pts, init
    jax.clear_caches()
    gc.collect()
    return out


def run(scale=1.0, dataset="uci-medium", *, duration_s=1.0,
        req_points=512, load=0.25, publishes=5, config=None,
        registry=None):
    prob = next(p for p in paper_suite if p.name == dataset)
    n = max(int(prob.n_points * scale), 2048)
    d, k = prob.n_dims, prob.k
    centroids = _fit_centroids(prob, n)

    reg = registry or MetricsRegistry()
    # tuned entry wins; otherwise the bench's saturation-oriented default
    # (deep batches amortize per-batch dispatch on the hot path)
    cfg = config or lookup_serve(k=k, d=d) or ServeConfig(max_batch=16384)
    index = CentroidIndex(centroids, obs=reg)
    rng = np.random.default_rng(7)
    pool, _, _ = make_points(max(4 * cfg.max_batch, 2 * n), d, k, seed=9)
    pool = np.ascontiguousarray(pool, np.float32)

    lat_ms: list = []
    sampled: list = []          # (query slice, labels, epoch) for parity
    epoch_centroids = {1: centroids}

    with ServeEngine(index, config=cfg, tune="off", obs=reg) as eng:
        # warm every bucket once so neither phase measures compiles
        for b in _buckets(cfg):
            eng.assign(pool[:b])

        # -- phase 1: closed-loop saturation (bulk requests) -------------
        # Device-resident request blocks, pre-staged OUTSIDE the timed
        # region — exactly the regime predict_bench measures in (its
        # pts are jnp.asarray'd once before the timed loop), so the
        # serve/predict ratio compares the two paths' compute, not a
        # host staging copy the predict row never pays. Each block is
        # exactly max_batch, so the engine's exact-fit path hands it
        # straight to the jitted assign (the zero-copy device-resident
        # submit). Host numpy traffic — which DOES pay one staging
        # copy per request — is what the open-loop phase measures.
        blocks = 4
        total = blocks * cfg.max_batch
        parts = [jnp.asarray(pool[i * cfg.max_batch:
                                  (i + 1) * cfg.max_batch])
                 for i in range(blocks)]
        for p in parts:
            p.block_until_ready()
        for f in [eng.submit(p) for p in parts]:
            f.result()                  # warm the parts into cache
        sat_s = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for f in [eng.submit(p) for p in parts]:
                f.result()
            sat_s = min(sat_s, time.perf_counter() - t0)
        pps = total / sat_s

        # -- phase 2: Poisson open-loop + concurrent refresh --------------
        rate = float(np.clip(load * pps / req_points, 100.0, 2500.0))
        stop_pub = threading.Event()

        def publisher():
            # small perturbations: the drift-ledger reuse path plus
            # genuinely different labels per epoch
            cur = centroids.copy()
            for _ in range(publishes):
                if stop_pub.wait(duration_s / (publishes + 1)):
                    return
                cur = cur + rng.standard_normal(
                    cur.shape).astype(np.float32) * 0.05
                ep = index.publish(cur)
                epoch_centroids[ep] = cur.copy()

        pub_t = threading.Thread(target=publisher)
        pub_t.start()
        pend = []
        done_at: dict = {}
        t_start = time.perf_counter()
        next_arrival = t_start
        i_req = 0
        while True:
            now = time.perf_counter()
            if now - t_start >= duration_s:
                break
            if now < next_arrival:
                time.sleep(min(next_arrival - now, 0.002))
                continue
            sched = next_arrival
            next_arrival += rng.exponential(1.0 / rate)
            lo = (i_req * 37) % (pool.shape[0] - req_points)
            fut = eng.submit(pool[lo:lo + req_points])
            # completion stamped by the engine thread's set_result, not
            # by whenever this thread gets around to reading the future
            fut.add_done_callback(
                lambda f, i=i_req: done_at.__setitem__(
                    i, time.perf_counter()))
            pend.append((i_req, sched, lo, fut))
            i_req += 1
        for i, sched, lo, fut in pend:
            fut.result()
        stop_pub.set()
        pub_t.join()
        for i, sched, lo, fut in pend:
            # open-loop latency is vs the SCHEDULED arrival — queueing
            # delay from falling behind the arrival process counts
            lat_ms.append((done_at[i] - sched) * 1e3)
            if i % 29 == 0:
                labels, epoch = fut.result()
                sampled.append((lo, labels, epoch))

    # -- exactness: every sampled response vs ITS epoch's oracle ---------
    parity = True
    oracles: dict = {}
    for lo, labels, epoch in sampled:
        if epoch not in oracles:
            oracles[epoch] = jnp.asarray(epoch_centroids[epoch])
        ref = np.asarray(jnp.argmin(pairwise_sq_dists(
            jnp.asarray(pool[lo:lo + req_points]), oracles[epoch]),
            axis=1))
        parity &= bool(np.array_equal(labels, ref))

    lat = np.sort(np.asarray(lat_ms))
    epochs_seen = sorted({e for _, _, e in sampled})
    return {
        "dataset": f"{dataset}-serve", "n": n, "d": d, "k": k,
        "backend": cfg.backend, "chunk": cfg.chunk,
        "max_batch": cfg.max_batch,
        "points_per_sec": pps,
        "p50_ms": float(lat[int(0.50 * (len(lat) - 1))]) if len(lat) else 0.0,
        "p99_ms": float(lat[int(0.99 * (len(lat) - 1))]) if len(lat) else 0.0,
        "requests": len(lat),
        "offered_rps": rate, "req_points": req_points,
        "publishes": index.publishes,
        "table_rebuilds": index.rebuilds,
        "table_reuses": index.reuses,
        "epochs_seen": len(epochs_seen),
        "labels_match_dense": parity,
    }, lat


def _buckets(cfg: ServeConfig):
    b, out = cfg.min_bucket, []
    while b <= cfg.max_batch:
        out.append(b)
        b *= 2
    return out


def write_json(row, path="BENCH_kmeans.json"):
    """Merge the serve record into the shared perf JSON."""
    payload = {}
    if os.path.exists(path):
        with open(path) as fh:
            payload = json.load(fh)
    payload["serve"] = row
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def write_histogram(lat_ms: np.ndarray, path: str) -> str:
    """Latency histogram JSONL (the CI serve-lane artifact): log-spaced
    bucket rows + one summary row."""
    edges = np.logspace(-1, 2.5, 36)      # 0.1ms .. ~316ms
    counts, _ = np.histogram(lat_ms, bins=edges)
    with open(path, "w") as fh:
        for lo, hi, c in zip(edges[:-1], edges[1:], counts):
            fh.write(json.dumps({"le_ms": round(float(hi), 4),
                                 "ge_ms": round(float(lo), 4),
                                 "count": int(c)}) + "\n")
        if len(lat_ms):
            fh.write(json.dumps({
                "summary": True, "n": int(len(lat_ms)),
                "p50_ms": float(np.percentile(lat_ms, 50)),
                "p99_ms": float(np.percentile(lat_ms, 99)),
                "max_ms": float(lat_ms.max())}) + "\n")
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--out", default="BENCH_kmeans.json",
                    help="perf JSON to merge the serve row into "
                         "('' disables)")
    ap.add_argument("--duration", type=float, default=1.0,
                    help="open-loop latency phase duration (s)")
    ap.add_argument("--check", action="store_true",
                    help="gate: exact parity + p99 ceiling; exit 1 on "
                         "failure")
    ap.add_argument("--p99-ceiling-ms", type=float, default=50.0,
                    help="--check fails when p99 exceeds this")
    ap.add_argument("--hist-out", default="obs_serve_latency.jsonl",
                    help="latency histogram JSONL ('' disables)")
    args = ap.parse_args(argv)

    row, lat = run(scale=args.scale, duration_s=args.duration)
    print("name,us_per_call,derived")
    print(f"serve/{row['dataset']},{1e6 * row['max_batch'] / row['points_per_sec']:.1f},"
          f"pps={row['points_per_sec']:.0f} p50={row['p50_ms']:.2f}ms "
          f"p99={row['p99_ms']:.2f}ms backend={row['backend']} "
          f"epochs={row['epochs_seen']} "
          f"parity={'OK' if row['labels_match_dense'] else 'FAIL'}")
    if args.hist_out:
        print(f"serve: latency histogram -> "
              f"{write_histogram(lat, args.hist_out)}")
    if args.out:
        write_json(row, args.out)
    if args.check:
        ok = True
        if not row["labels_match_dense"]:
            print("serve: PARITY FAILED vs per-epoch dense oracle")
            ok = False
        if row["p99_ms"] > args.p99_ceiling_ms:
            print(f"serve: p99 {row['p99_ms']:.2f}ms exceeds ceiling "
                  f"{args.p99_ceiling_ms:.1f}ms")
            ok = False
        if row["points_per_sec"] <= 0 or row["requests"] == 0:
            print("serve: no traffic served")
            ok = False
        print(f"serve: check {'OK' if ok else 'FAILED'}")
        sys.exit(0 if ok else 1)
    return row


if __name__ == "__main__":
    from repro.platform import use_compile_cache
    use_compile_cache()
    main()
