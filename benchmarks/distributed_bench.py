"""Distributed engine benchmark: sharded compact vs sharded dense.

Runs the uci-medium-class shape through ``distributed_yinyang`` on a
mesh over every device of the process. ``benchmarks/run.py`` calls
:func:`main` in its own process on the devices that exist (at least
two, e.g. the four chips of a v5e host). The CPU run is its own
command, ``python -m benchmarks.distributed_bench``: only as the
process entry point does this module force four CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``, set below
before jax initialises).

Reports, and records under the ``"distributed"`` key of
``BENCH_kmeans.json``:

* ``dense_ms`` / ``compact_ms`` — wall-clock of the legacy masked-dense
  per-shard pass vs the capacity-bucketed compaction inside the
  ``shard_map`` body (the PR 4 tentpole);
* ``work_reduction`` — psum'd ``distance_evals`` vs the dense
  equivalent (N*K per iteration + the init pass): the per-shard filter
  work saving surviving distribution (must stay > 1.0 — CI gates on
  the committed value via ``benchmarks/run.py --check``);
* ``assignments_match`` — sharded-compact vs sharded-dense parity
  (bit-identical by construction: same psum reduction order);
* ``inertia_rel_err`` — vs the single-device engine fixed point.

``--check`` exits non-zero when parity fails or the measured work
reduction is <= 1.0 — the multi-device CI lane runs it directly.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_FORCE = "--xla_force_host_platform_device_count"
if __name__ == "__main__" and _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FORCE}=4").strip()

import jax              # noqa: E402  (after the device-count env var)
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

from repro.configs.kpynq import paper_suite               # noqa: E402
from repro.core import (distributed_yinyang, engine_fit,  # noqa: E402
                        kmeans_plusplus, make_mesh)
from repro.data import make_points                        # noqa: E402


def _time_best(fn, repeats=3):
    out = fn()                          # compile + warm caches
    jax.block_until_ready(out.centroids)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        r = fn()
        jax.block_until_ready(r.centroids)
        best = min(best, time.perf_counter() - t0)
    return out, best


def run(scale=1.0, dataset="uci-medium", repeats=3):
    prob = next(p for p in paper_suite if p.name == dataset)
    n = max(int(prob.n_points * scale), 2048)
    n_dev = jax.device_count()
    pts_np, _, _ = make_points(n, prob.n_dims, prob.k, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, prob.k)
    mesh = make_mesh(n_dev)

    kw = dict(n_groups=prob.n_groups, max_iters=prob.max_iters,
              tol=prob.tol)
    r_dense, t_dense = _time_best(
        lambda: distributed_yinyang(pts, init, mesh, backend="dense",
                                    **kw), repeats)
    r_comp, t_comp = _time_best(
        lambda: distributed_yinyang(pts, init, mesh, backend="compact",
                                    **kw), repeats)
    r_single = engine_fit(pts, init, backend="compact", tune="off", **kw)
    # telemetry pass OUTSIDE the timed loops: per-shard rings + skew
    # (results are bit-identical, so the rings describe the timed fit)
    _, dstats = distributed_yinyang(pts, init, mesh, backend="compact",
                                    return_stats=True, **kw)

    iters = int(r_comp.n_iters)
    # dense equivalent: the init pass + one full (N, K) pass per
    # iteration plus the epilogue — same convention as the single-
    # device rows (Lloyd's counter)
    dense_equiv = float(n) * prob.k * (iters + 1)
    evals = float(r_comp.distance_evals)
    inertia_s = float(r_single.inertia)
    return {
        "dataset": f"{dataset}-dist", "n": n, "d": prob.n_dims,
        "k": prob.k, "devices": n_dev, "iters": iters,
        "dense_ms": t_dense * 1e3, "compact_ms": t_comp * 1e3,
        "speedup_vs_dense": t_dense / t_comp,
        "distance_evals": evals,
        "dense_equiv_evals": dense_equiv,
        "work_reduction": dense_equiv / max(evals, 1.0),
        "assignments_match": bool(np.array_equal(
            np.asarray(r_dense.assignments),
            np.asarray(r_comp.assignments))),
        "inertia": float(r_comp.inertia),
        "inertia_rel_err": abs(float(r_comp.inertia) - inertia_s)
        / max(inertia_s, 1e-12),
        # ring summary incl. per-shard work skew (max/mean evals per
        # iteration across shards; 1.0 = perfectly balanced)
        "telemetry": dstats.telemetry(),
    }


def write_json(row, path="BENCH_kmeans.json"):
    """Merge the distributed record into the shared perf JSON."""
    payload = {}
    if os.path.exists(path):
        with open(path) as fh:
            payload = json.load(fh)
    payload["distributed"] = row
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--dataset", default="uci-medium")
    ap.add_argument("--json", "--out", dest="json",
                    default="BENCH_kmeans.json",
                    help="perf record to merge into ('' disables)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when compact/dense parity fails "
                         "or work_reduction <= 1.0 (CI gate)")
    args = ap.parse_args(argv)
    if jax.device_count() < 2:
        print("distributed_bench: single device — run as "
              f"`python -m benchmarks.distributed_bench` (or set "
              f"XLA_FLAGS={_FORCE}=4)", file=sys.stderr)
        sys.exit(2)

    row = run(scale=args.scale, dataset=args.dataset)
    print("name,us_per_call,derived")
    print(f"distributed/{row['dataset']},{row['compact_ms'] * 1e3:.1f},"
          f"devices={row['devices']} "
          f"vs_dense={row['speedup_vs_dense']:.2f}x "
          f"work_red={row['work_reduction']:.2f}x "
          f"parity={'OK' if row['assignments_match'] else 'FAIL'} "
          f"inertia_err={row['inertia_rel_err']:.2e} "
          f"iters={row['iters']} "
          f"skew={(row['telemetry'] or {}).get('max_shard_skew', 1.0):.2f}")
    if args.json:
        write_json(row, args.json)
    if args.check:
        ok = row["assignments_match"] and row["work_reduction"] > 1.0 \
            and row["inertia_rel_err"] < 1e-3
        print(f"check: distributed parity+work gate -> "
              f"{'OK' if ok else 'REGRESSION'}")
        sys.exit(0 if ok else 1)


if __name__ == "__main__":
    from repro.platform import use_compile_cache
    use_compile_cache()
    main()
