"""Resilience benchmark: checkpointed streaming fit vs plain fit.

Three measurements on the uci-medium streaming config:

* ``stream_ms``    — plain ``fit_stream`` wall time (no checkpoints);
* ``resilient_ms`` — the same fit under ``resilient=True`` with async
  checkpoints every ``ckpt_every`` batches (plus the terminal sync
  save): the price of crash-safety;
* ``replay_exact`` — an injected mid-epoch failure, restore from the
  newest async checkpoint, deterministic replay of the ``(seed,
  shard)`` stream — final centroids must be bit-identical to the
  uninterrupted fit.

``bit_exact`` asserts the failure-free checkpointed fit equals the
plain fit bitwise (checkpointing must be a pure observer), and the
``benchmarks/run.py --check`` resilience gate additionally bounds
``resilient_ms <= stream_ms * 1.10 + 5ms``.

Merged into BENCH_kmeans.json under the ``"resilience"`` key.
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import numpy as np

from repro.configs.kpynq import paper_suite
from repro.data import PointStream, make_points
from repro.runtime import FailureInjector
from repro.streaming import StreamingKMeans


def run(scale=1.0, epochs=2, shard_size=2048, dataset="uci-medium",
        ckpt_every=4, repeats=2):
    prob = next(p for p in paper_suite if p.name == dataset)
    n = max(int(prob.n_points * scale), 2048)
    pts_np, _, _ = make_points(n, prob.n_dims, prob.k, seed=0)
    stream = PointStream(shard_size=min(shard_size, n), data=pts_np)

    def fresh():
        return StreamingKMeans(prob.k, n_groups=prob.n_groups, seed=1,
                               init_size=min(2 * shard_size, n))

    # warmup: compile every kernel once so neither timed mode pays JIT
    fresh().fit_stream(stream, epochs=1)

    # plain vs checkpointed, best-of-``repeats`` with a fresh estimator
    # per repetition (a streaming fit mutates its estimator, so reruns
    # on the same object would measure the warm-cache epoch instead)
    t_plain = float("inf")
    for _ in range(repeats):
        skm_plain = fresh()
        t0 = time.perf_counter()
        skm_plain.fit_stream(stream, epochs=epochs)
        t_plain = min(t_plain, time.perf_counter() - t0)

    t_ck = float("inf")
    for _ in range(repeats):
        with tempfile.TemporaryDirectory() as d:
            skm_ck = fresh()
            t0 = time.perf_counter()
            skm_ck.fit_stream(stream, epochs=epochs, resilient=True,
                              ckpt_dir=d, ckpt_every=ckpt_every)
            t_ck = min(t_ck, time.perf_counter() - t0)

    bit_exact = (np.array_equal(np.asarray(skm_plain.cluster_centers_),
                                np.asarray(skm_ck.cluster_centers_))
                 and np.array_equal(np.asarray(skm_plain.counts_),
                                    np.asarray(skm_ck.counts_)))

    # chaos row: crash mid-epoch (off the checkpoint lattice so the
    # replay path actually runs), restore + replay, compare bitwise
    n_steps = max(epochs, 1) * len(stream)
    fail_at = max(1, n_steps // 2)
    if fail_at % ckpt_every == 0:
        fail_at += 1
    with tempfile.TemporaryDirectory() as d:
        skm_ch = fresh()
        skm_ch.fit_stream(stream, epochs=epochs, resilient=True,
                          ckpt_dir=d, ckpt_every=ckpt_every,
                          injector=FailureInjector(fail_at=(fail_at,)))
    st = skm_ch.stats_
    replay_exact = (st.restores >= 1
                    and np.array_equal(np.asarray(skm_plain.cluster_centers_),
                                       np.asarray(skm_ch.cluster_centers_))
                    and np.array_equal(np.asarray(skm_plain.counts_),
                                       np.asarray(skm_ch.counts_)))

    return {
        "dataset": f"{dataset}-resilient", "n": n, "d": prob.n_dims,
        "k": prob.k, "shard_size": stream.shard_size, "epochs": epochs,
        "batches": n_steps, "ckpt_every": ckpt_every,
        "stream_ms": t_plain * 1e3,
        "resilient_ms": t_ck * 1e3,
        "save_overhead_pct": (t_ck / max(t_plain, 1e-12) - 1.0) * 100.0,
        "ckpt_saves": skm_ck.stats_.ckpt_saves,
        "bit_exact": bool(bit_exact),
        "fail_at": fail_at,
        "restores": st.restores,
        "replayed_batches": st.replayed_batches,
        "replay_exact": bool(replay_exact),
    }


def write_json(row, path="BENCH_kmeans.json"):
    """Merge the resilience record into the shared perf JSON."""
    payload = {}
    if os.path.exists(path):
        with open(path) as fh:
            payload = json.load(fh)
    payload["resilience"] = row
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
    return path


def main(scale=1.0, epochs=2, json_path=None):
    row = run(scale=scale, epochs=epochs)
    print("name,us_per_call,derived")
    print(f"resilience/{row['dataset']},{row['resilient_ms'] * 1e3:.1f},"
          f"stream_ms={row['stream_ms']:.1f} "
          f"overhead={row['save_overhead_pct']:+.1f}% "
          f"saves={row['ckpt_saves']} "
          f"bit_exact={'OK' if row['bit_exact'] else 'FAIL'} "
          f"replay_exact={'OK' if row['replay_exact'] else 'FAIL'} "
          f"restores={row['restores']} replayed={row['replayed_batches']}")
    if json_path:
        write_json(row, json_path)
    return row


if __name__ == "__main__":
    from repro.platform import use_compile_cache
    use_compile_cache()
    main(json_path="BENCH_kmeans.json")
