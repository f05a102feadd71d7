"""Benchmark harness: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--out PATH]
  PYTHONPATH=src python -m benchmarks.run --quick --tune   # retune first
  PYTHONPATH=src python -m benchmarks.run --check        # CI perf gate

Prints ``name,us_per_call,derived`` CSV per line, and writes the
K-means perf record to ``BENCH_kmeans.json`` (per-dataset ``lloyd_ms``,
``engine_ms``, ``speedup``, ``work_reduction``, winning ``tuned``
config + suite means, plus the ``streaming`` and ``distributed``
subsystem records — the latter measured in this process on the devices
that exist, and only when there are at least two; the forced
multi-device CPU run is ``python -m benchmarks.distributed_bench``) so
the perf trajectory is tracked across PRs. Every section runs in this
one process (a chip belongs to one process), and a section that fails
fails the run.

``--tune`` refreshes the engine's per-(platform, N, K, D) tuning cache
(``benchmarks/autotune.py`` -> :mod:`repro.tune`) for the suite's
problem signatures BEFORE measuring, so the ``engine`` rows run the
tuned configurations.

``--check`` is the regression gate:

* re-measures the quick suite and compares ``mean_speedup`` against
  the committed record (within ``--check-tolerance``, timing noise
  being what it is);
* requires the COMMITTED record itself to show the engine at no worse
  than 5% behind Lloyd (``engine_ms <= lloyd_ms * 1.05 + 0.25``; the
  absolute term is the wrapper's fixed dispatch cost, visible only on
  sub-ms rows) on every quick-suite dataset — the deterministic
  wall-clock contract of ISSUE 3 (the engine's work-efficiency must
  not cost wall-clock);
* requires the streaming fit's inertia gap to stay within 5% of the
  batch engine;
* requires the committed ``distributed`` record (when present) to keep
  compact/dense parity and a per-shard work reduction > 1.0;
* smoke-measures the tiled predict path (``predict_bench``): exact
  parity with the dense argmin gates, and fresh throughput must stay
  above the committed row * ``--check-tolerance`` (the drift gate —
  the committed predict row is a real baseline, not a log line);
* measures the serving subsystem (``serve_bench``): per-epoch oracle
  parity under a concurrent publisher gates, the COMMITTED serve row
  must show >= 8x the committed predict row's points/s (the ISSUE 10
  tentpole claim), fresh serve throughput must stay above the
  committed row * tolerance, and the open-loop p99 must stay under
  a machine-aware ceiling (max of ``--serve-p99-ceiling-ms`` and the
  committed row's p99 / tolerance);
* runs the deterministic weighted-parity gate: uniform ``sample_weight``
  bit-identical to unweighted on every backend, integer weights ==
  duplicated points.

* runs the telemetry-overhead gate: ``engine_ms`` with the telemetry
  ring on must stay within 3% (+0.5ms absolute, timer floor) of the
  ring off, interleaved best-of — observability must be ~free;
* requires the committed record to carry its ``provenance`` block
  (git sha, jax version, platform, device count, timestamp) and a
  ``telemetry`` summary per dataset row.

Every gate reports through one :class:`repro.obs.MetricsRegistry`
(gauge ``check_gate_ok{gate=...}`` + a ``gate`` event each), so every
failure names itself — including the streaming-only exit-3 path — and
the whole run exports ``obs_events.jsonl`` / ``obs_metrics.prom`` plus
a Perfetto trace dir (``obs_trace/``) as CI artifacts.

Exit codes are per-gate so CI logs say which tripped: 0 = all OK,
1 = any engine-side gate regressed (the ``gate[...]`` lines name
them), **3 = ONLY the streaming inertia gap regressed** (speedups all
healthy — a subsystem-specific failure, not an engine regression),
2 = no committed record.
"""
import argparse
import sys


def weighted_parity_gate() -> bool:
    """Deterministic sample-weight gate: uniform weights must be
    BIT-IDENTICAL to the unweighted fit on every engine backend, and
    integer weights must land on the duplicated-points fixed point.
    Pure correctness (no timing), so it either holds or the weight
    threading regressed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine_fit, kmeans_plusplus
    from repro.data import make_points

    pts_np, _, _ = make_points(1200, 8, 12, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 12)
    ok = True
    for backend in ("oracle", "compact", "lloyd"):
        r0 = engine_fit(pts, init, max_iters=30, tol=1e-5,
                        backend=backend, tune="off")
        r1 = engine_fit(pts, init, max_iters=30, tol=1e-5,
                        backend=backend, tune="off",
                        sample_weight=jnp.ones((1200,)))
        bit = np.array_equal(np.asarray(r0.assignments),
                             np.asarray(r1.assignments)) and \
            float(r0.inertia) == float(r1.inertia)
        ok &= bit
        print(f"check: weighted-parity uniform/{backend}: "
              f"{'OK' if bit else 'REGRESSION'}")
    rng = np.random.default_rng(0)
    wts = rng.integers(1, 4, size=1200)
    r_w = engine_fit(pts, init, max_iters=40, tol=1e-6,
                     backend="compact", tune="off",
                     sample_weight=jnp.asarray(wts, jnp.float32))
    r_d = engine_fit(jnp.asarray(np.repeat(pts_np, wts, axis=0)), init,
                     max_iters=40, tol=1e-6, backend="compact",
                     tune="off")
    dup = bool(np.allclose(np.asarray(r_w.centroids),
                           np.asarray(r_d.centroids), atol=1e-3))
    ok &= dup
    print(f"check: weighted-parity duplication==int-weights: "
          f"{'OK' if dup else 'REGRESSION'}")
    return ok


def telemetry_overhead_gate(registry):
    """Observability must be ~free: interleaved best-of wall-clock of
    the same engine fit with the telemetry ring ON (incl. the one-shot
    drain + stats build) vs OFF. Gate: ``on <= off * 1.03 + 0.5ms``
    (the absolute term is the timer/dispatch floor on sub-ms fits).
    Returns ``(ok, detail_str, off_s, on_s)``."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import engine_fit, kmeans_plusplus
    from repro.data import make_points
    from repro.obs import ObsConfig

    pts_np, _, _ = make_points(8000, 16, 32, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 32)
    obs_cfg = ObsConfig(registry=registry)
    # return_stats on BOTH sides: stats construction predates obs, so
    # the measured delta is exactly the telemetry (ring threading +
    # one-shot drain + registry publish), not the stats object
    kw = dict(max_iters=25, tol=0.0, backend="compact", tune="off",
              return_stats=True)

    def run_off():
        r, _ = engine_fit(pts, init, **kw)
        jax.block_until_ready(r.centroids)

    def run_on():
        r, _ = engine_fit(pts, init, obs=obs_cfg, **kw)
        jax.block_until_ready(r.centroids)

    run_off(), run_on()                   # compile + warm caches
    best = [float("inf"), float("inf")]
    done, spent = 0, 0.0
    # deep sampling: the delta under test is sub-ms, so the best-of
    # must actually reach both floors or noise decides the gate
    while done < 20 or (spent < 3.0 and done < 60):
        for j, f in enumerate((run_off, run_on)):
            t0 = time.perf_counter()
            f()
            dt = time.perf_counter() - t0
            best[j] = min(best[j], dt)
            spent += dt
        done += 1
    t_off, t_on = best
    ok = t_on <= t_off * 1.03 + 0.5e-3
    detail = (f"off={t_off * 1e3:.2f}ms on={t_on * 1e3:.2f}ms "
              f"ratio={t_on / max(t_off, 1e-12):.3f} "
              f"(limit 1.03 + 0.5ms)")
    return ok, detail, t_off, t_on


def check(args) -> None:
    import json

    from repro.obs import MetricsRegistry, profile

    from . import (kmeans_speedup, predict_bench, resilience_bench,
                   serve_bench, streaming_bench)

    reg = MetricsRegistry()
    gates: dict = {}          # name -> ok, in report order

    def gate(name: str, ok, detail: str = "") -> bool:
        """Single reporting funnel: every gate lands in the registry
        (gauge + event) AND prints one self-naming line."""
        ok = bool(ok)
        gates[name] = ok
        reg.gauge("check_gate_ok", "1 = perf gate passed",
                  labels={"gate": name}).set(1.0 if ok else 0.0)
        reg.log_event("gate", gate=name, ok=ok, detail=detail)
        print(f"check: gate[{name}] {'OK' if ok else 'REGRESSION'}"
              + (f" ({detail})" if detail else ""))
        return ok

    def export_artifacts() -> None:
        """CI artifacts: the event log (every gate + every obs-enabled
        fit), the Prometheus snapshot, and a Perfetto trace of one
        engine fit carrying the kpynq/* phase annotations."""
        print(f"check: obs event log -> {reg.export_jsonl('obs_events.jsonl')}")
        print(f"check: obs metrics  -> "
              f"{reg.export_prometheus('obs_metrics.prom')}")

    def finish() -> None:
        export_artifacts()
        failed = [name for name, ok in gates.items() if not ok]
        if not failed:
            sys.exit(0)
        if failed == ["streaming-gap"]:
            # distinct code: ONLY the streaming subsystem tripped — the
            # engine gates above are all healthy, so CI can label the
            # failure precisely instead of reading it as a perf
            # regression
            print("check: FAILED gate(s): streaming-gap (exit 3)")
            sys.exit(3)
        print(f"check: FAILED gate(s): {', '.join(failed)} (exit 1)")
        sys.exit(1)

    try:
        with open(args.json) as fh:
            committed = json.load(fh)
    except FileNotFoundError:
        print(f"check: no committed record at {args.json}; run the "
              f"benchmark first", file=sys.stderr)
        reg.log_event("gate", gate="committed-record", ok=False,
                      detail=f"missing {args.json}")
        reg.export_jsonl("obs_events.jsonl")
        sys.exit(2)

    # the committed record must say where it came from and what the
    # engine did per dataset — both deterministic record-shape gates
    prov = committed.get("provenance") or {}
    gate("provenance",
         isinstance(prov, dict) and "git_sha" in prov
         and "jax_version" in prov and "timestamp" in prov,
         f"git={prov.get('git_sha', 'MISSING')!s:.12} "
         f"jax={prov.get('jax_version', 'MISSING')}")
    gate("telemetry",
         bool(committed.get("datasets"))
         and all("telemetry" in r for r in committed["datasets"]),
         "per-dataset ring summaries present")

    # committed-record wall-clock gate: the engine row of every dataset
    # must be within 5% of its Lloyd baseline (deterministic — no
    # re-measurement; the record is only committed when it holds). The
    # 0.25ms absolute term covers the engine wrapper's fixed dispatch
    # overhead, which is structural (not a regression) on sub-ms
    # Lloyd-routed rows and negligible everywhere else.
    wall_ok = True
    worst = 0.0
    for row in committed.get("datasets", []):
        ratio = row["engine_ms"] / max(row["lloyd_ms"], 1e-9)
        worst = max(worst, ratio)
        ok = row["engine_ms"] <= row["lloyd_ms"] * 1.05 + 0.25
        wall_ok &= ok
        print(f"check: committed {row['dataset']}: engine/lloyd="
              f"{ratio:.3f} (limit 1.05 + 0.25ms) -> "
              f"{'OK' if ok else 'REGRESSION'}")
    gate("wall-clock", wall_ok,
         f"worst engine/lloyd={worst:.3f} (limit 1.05 + 0.25ms)")

    # committed distributed record: parity is structural and the
    # work reduction is the tentpole claim — both deterministic
    drow = committed.get("distributed")
    if drow:
        gate("distributed",
             drow.get("assignments_match", False)
             and drow.get("work_reduction", 0.0) > 1.0,
             f"parity={'OK' if drow.get('assignments_match') else 'FAIL'} "
             f"work_reduction={drow.get('work_reduction', 0.0):.2f}x "
             f"(must be > 1.0)")

    scale = committed.get("scale", 0.1)
    if args.tune:
        from . import autotune
        autotune.tune_suite(scale=scale)

    # re-measure at the committed record's scale: speedups at different
    # problem sizes are incommensurable (tiny fits auto-route to Lloyd)
    rows = kmeans_speedup.run(scale=scale)
    fresh = kmeans_speedup.summarize(rows)["mean_speedup"]
    committed_rows = {r["dataset"]: r for r in committed.get("datasets", [])}
    print("check: dataset            fresh   committed")
    for r in rows:
        ref_row = committed_rows.get(r["dataset"], {})
        print(f"check:   {r['dataset']:<16} "
              f"{r['speedup']:7.3f}x  "
              f"{ref_row.get('speedup', float('nan')):7.3f}x")
    ref = committed["mean_speedup"]
    floor = ref * args.check_tolerance
    gate("mean_speedup", fresh >= floor,
         f"fresh={fresh:.3f} committed={ref:.3f} (scale={scale}) "
         f"floor={floor:.3f}")

    # observability must not cost wall-clock: ring on vs off,
    # interleaved best-of, on the same compiled problem
    ov_ok, ov_detail, _, _ = telemetry_overhead_gate(reg)
    gate("telemetry-overhead", ov_ok, ov_detail)

    # predict row: the tiled PassCore assign must be exact (parity with
    # the dense argmin is structural), and fresh throughput must hold
    # the committed row within tolerance — the committed predict row is
    # the serve gate's 8x denominator, so drift here is gated, not
    # just logged
    prow = predict_bench.run(scale=scale)
    cpred = (committed.get("predict") or {}).get("points_per_sec", 0.0)
    pred_floor = cpred * args.check_tolerance
    gate("predict",
         prow["labels_match_dense"] and prow["points_per_sec"] > 0
         and prow["points_per_sec"] >= pred_floor,
         f"pps={prow['points_per_sec']:.0f} committed={cpred:.0f} "
         f"floor={pred_floor:.0f} parity="
         f"{'OK' if prow['labels_match_dense'] else 'FAIL'}")

    # serving subsystem: batched throughput + swap consistency.
    # serve-parity is structural (every sampled response must match
    # ITS OWN epoch's dense oracle exactly, under a concurrent
    # publisher). serve-throughput is the tentpole claim: the
    # COMMITTED serve row >= 8x the committed predict row
    # (deterministic, record-shape), and the fresh measurement must
    # hold the committed row within tolerance.
    svrow, _ = serve_bench.run(scale=scale)
    cserve = (committed.get("serve") or {}).get("points_per_sec", 0.0)
    ratio = cserve / max(cpred, 1e-9)
    serve_floor = cserve * args.check_tolerance
    gate("serve-parity",
         svrow["labels_match_dense"] and svrow["requests"] > 0
         and svrow["epochs_seen"] >= 1,
         f"parity={'OK' if svrow['labels_match_dense'] else 'FAIL'} "
         f"requests={svrow['requests']} epochs={svrow['epochs_seen']}")
    gate("serve-throughput",
         cserve > 0 and ratio >= 8.0
         and svrow["points_per_sec"] >= serve_floor,
         f"committed serve/predict={ratio:.2f}x (need >=8) "
         f"fresh={svrow['points_per_sec']:.0f} floor={serve_floor:.0f}")
    # p99 is the one wall-clock-fresh latency gate, so it must absorb
    # shared-runner noise: the ceiling is the committed row's p99
    # widened by the check tolerance, floored at --serve-p99-ceiling-ms
    # so a very fast committed row never produces a hair-trigger gate
    cp99 = (committed.get("serve") or {}).get("p99_ms", 0.0)
    p99_ceiling = max(args.serve_p99_ceiling_ms,
                      cp99 / max(args.check_tolerance, 1e-9))
    gate("serve-p99", svrow["p99_ms"] <= p99_ceiling,
         f"p50={svrow['p50_ms']:.2f}ms p99={svrow['p99_ms']:.2f}ms "
         f"(ceiling {p99_ceiling:.1f}ms = max(floor "
         f"{args.serve_p99_ceiling_ms:.1f}ms, committed {cp99:.2f}ms "
         f"/ tolerance {args.check_tolerance}))")

    gate("weighted-parity", weighted_parity_gate())

    # resilience: the checkpointed streaming fit must be a pure
    # observer (bit-exact vs the plain fit), crash + restore + replay
    # must land on the identical centroids, and the async-save price
    # must stay under 10% + 5ms of the plain streaming wall time.
    # Placed BEFORE streaming-gap so the `failed == ["streaming-gap"]`
    # subsystem exit code below stays precise.
    rrow = resilience_bench.run(scale=scale, epochs=2)
    res_budget_ms = rrow["stream_ms"] * 1.10 + 5.0
    gate("resilience",
         rrow["bit_exact"] and rrow["replay_exact"]
         and rrow["resilient_ms"] <= res_budget_ms,
         f"bit_exact={'OK' if rrow['bit_exact'] else 'FAIL'} "
         f"replay_exact={'OK' if rrow['replay_exact'] else 'FAIL'} "
         f"resilient={rrow['resilient_ms']:.1f}ms "
         f"budget={res_budget_ms:.1f}ms "
         f"(stream={rrow['stream_ms']:.1f}ms * 1.10 + 5ms) "
         f"saves={rrow['ckpt_saves']} replayed={rrow['replayed_batches']}")

    # streaming LAST among the gates so `failed == ["streaming-gap"]`
    # cleanly selects the subsystem-specific exit code
    srow = streaming_bench.run(scale=scale, epochs=3)
    gate("streaming-gap", srow["inertia_gap"] <= 0.05,
         f"inertia_gap={srow['inertia_gap'] * 100:+.2f}% (limit +5%)")

    # perfetto trace artifact: one profiled engine fit, phases annotated
    import jax
    import jax.numpy as jnp

    from repro.core import engine_fit, kmeans_plusplus
    from repro.data import make_points
    pts_np, _, _ = make_points(4096, 8, 16, seed=0)
    pts = jnp.asarray(pts_np)
    init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 16)
    _, tdir = profile(engine_fit, pts, init, max_iters=10,
                      backend="compact", tune="off",
                      trace_dir="obs_trace", registry=reg)
    print(f"check: perfetto trace -> {tdir}")

    finish()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller problem sizes (CI-friendly)")
    ap.add_argument("--json", "--out", dest="json",
                    default="BENCH_kmeans.json",
                    help="path for the machine-readable K-means record "
                         "('' disables)")
    ap.add_argument("--check", action="store_true",
                    help="perf regression gate: compare fresh --quick "
                         "results against the committed record; exit 1 "
                         "on regression")
    ap.add_argument("--check-tolerance", type=float, default=0.6,
                    help="--check fails when fresh mean_speedup drops "
                         "below committed * this factor (default 0.6 — "
                         "shared-CI timing noise is large)")
    ap.add_argument("--serve-p99-ceiling-ms", type=float, default=50.0,
                    help="minimum serve-p99 ceiling; the gate uses "
                         "max(this, committed p99 / check-tolerance) "
                         "so loaded runners don't flake on a fresh "
                         "wall-clock percentile")
    ap.add_argument("--tune", action="store_true",
                    help="refresh the engine tuning cache "
                         "(benchmarks/autotune.py) for the suite's "
                         "problem signatures before measuring")
    args = ap.parse_args()
    if args.check:
        check(args)
        return
    scale = 0.1 if args.quick else 1.0

    from . import filter_efficiency, group_sweep, kernel_bench
    from . import (kmeans_speedup, predict_bench, resilience_bench,
                   roofline_report, streaming_bench)

    if args.tune:
        from . import autotune
        print("# === autotune: engine configuration search ===",
              flush=True)
        autotune.main(scale=scale, verbose=False)

    print("# === paper Table: KPynq vs standard K-means ===", flush=True)
    kmeans_speedup.main(scale=scale, json_path=args.json or None)
    print("# === streaming / mini-batch subsystem ===", flush=True)
    streaming_bench.main(scale=scale, json_path=args.json or None)
    print("# === predict path (tiled PassCore assign) ===", flush=True)
    predict_bench.main(scale=scale, json_path=args.json or None)
    print("# === serve path (batched assign, epoch-swapped index) ===",
          flush=True)
    from . import serve_bench
    serve_bench.main(["--scale", str(scale), "--out", args.json or "",
                      "--hist-out", ""])
    print("# === resilience (checkpointed streaming, crash replay) ===",
          flush=True)
    resilience_bench.main(scale=scale, json_path=args.json or None)
    import jax
    n_dev = jax.device_count()
    if n_dev >= 2:
        print(f"# === distributed engine ({n_dev} devices) ===",
              flush=True)
        from . import distributed_bench
        distributed_bench.main(["--scale", str(scale),
                                "--out", args.json or ""])
    else:
        print("# === distributed engine: not run on 1 device (CPU: "
              "python -m benchmarks.distributed_bench) ===", flush=True)
    print("# === filter efficiency (multi-level filter rates) ===",
          flush=True)
    filter_efficiency.main()
    print("# === kernel microbench + block-skip model ===", flush=True)
    kernel_bench.main()
    print("# === tunable parameters: group-count / K ablation ===",
          flush=True)
    group_sweep.main()
    print("# === roofline table (from dry-run cache) ===", flush=True)
    roofline_report.main()


if __name__ == "__main__":
    from repro.platform import use_compile_cache
    use_compile_cache()
    main()
