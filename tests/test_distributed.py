"""Distributed behaviour on a multi-device (forced 8-CPU) runtime.

jax locks the device count at first init, so these tests run in
subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys
import textwrap

import pytest

# every test here spawns a forced-multi-device subprocess — CI runs
# them in the dedicated multi-device lane
pytestmark = pytest.mark.multidevice

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    script = textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    return out.stdout


def test_sharded_compact_parity_matrix():
    """The tentpole contract: the capacity-bucketed compaction inside
    the shard_map body is EXACT — bit-identical assignments/inertia to
    the sharded masked-dense oracle (same psum reduction order), with
    and without int8 partial-sums compression, and it matches the
    single-device engine's fixed point; psum'd distance_evals show the
    per-shard filter actually skipping work."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed_yinyang, engine_fit, \\
            kmeans_plusplus
        from repro.data import make_points
        pts_np, _, _ = make_points(4096, 32, 64, seed=0)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 64)
        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(max_iters=40, tol=1e-5)

        for compress in (False, True):
            r_d = distributed_yinyang(pts, init, mesh, backend="dense",
                                      compress=compress, **kw)
            r_c = distributed_yinyang(pts, init, mesh, backend="compact",
                                      compress=compress, **kw)
            assert np.array_equal(np.asarray(r_d.assignments),
                                  np.asarray(r_c.assignments)), compress
            assert float(r_d.inertia) == float(r_c.inertia), compress
            assert int(r_d.n_iters) == int(r_c.n_iters), compress

        r_c = distributed_yinyang(pts, init, mesh, backend="compact", **kw)
        r_s = engine_fit(pts, init, backend="compact", tune="off", **kw)
        assert np.array_equal(np.asarray(r_c.assignments),
                              np.asarray(r_s.assignments))
        np.testing.assert_allclose(float(r_c.inertia), float(r_s.inertia),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(r_c.centroids),
                                   np.asarray(r_s.centroids), atol=1e-4)
        # work-efficiency: psum'd evals beat the dense equivalent
        dense_equiv = 4096 * 64 * (int(r_c.n_iters) + 1)
        assert float(r_c.distance_evals) < dense_equiv, \\
            (float(r_c.distance_evals), dense_equiv)
        print("PARITY-MATRIX-OK")
    """)


def test_sharded_compact_uneven_and_all_survivor_shards():
    """Uneven N (sentinel padding) and a pathological shard whose
    points never filter (uniform noise -> every point a candidate ->
    that shard rides the TOP capacity bucket while the clustered
    shards downshift): shard-divergent bucket levels must not desync
    the collectives or perturb the fixed point."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed_yinyang, engine_fit, \\
            kmeans_plusplus
        from repro.data import make_points
        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(max_iters=40, tol=1e-5)

        # uneven: N=4001 over 8 shards (pad rows are sentinels)
        pts_np, _, _ = make_points(4001, 16, 24, seed=3)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 24)
        r_c = distributed_yinyang(pts, init, mesh, backend="compact", **kw)
        r_s = engine_fit(pts, init, backend="compact", tune="off", **kw)
        assert r_c.assignments.shape == (4001,)
        assert np.array_equal(np.asarray(r_c.assignments),
                              np.asarray(r_s.assignments))
        np.testing.assert_allclose(float(r_c.inertia), float(r_s.inertia),
                                   rtol=1e-5)

        # all-survivors shard: shard 0 = structureless uniform noise
        # (bounds never prune it), shards 1..7 = tight clusters
        rng = np.random.default_rng(7)
        clustered, _, _ = make_points(3584, 16, 24, seed=4,
                                      cluster_std=0.3)
        noise = rng.uniform(-20, 20, size=(512, 16)).astype(np.float32)
        pts = jnp.asarray(np.concatenate([noise, clustered], axis=0))
        init = kmeans_plusplus(jax.random.PRNGKey(2), pts, 24)
        r_d = distributed_yinyang(pts, init, mesh, backend="dense", **kw)
        r_c = distributed_yinyang(pts, init, mesh, backend="compact", **kw)
        assert np.array_equal(np.asarray(r_d.assignments),
                              np.asarray(r_c.assignments))
        assert float(r_d.inertia) == float(r_c.inertia)
        print("UNEVEN-SURVIVOR-OK")
    """)


def test_sharded_streaming_matches_local():
    """StreamingKMeans(mesh=...): the distributed partial_fit (psum'd
    batch sums/counts feeding the decayed EMA) matches the local step
    on counts and distance evals exactly, and on centroids to psum
    rounding; uneven batches exercise the sentinel padding."""
    _run("""
        import jax, numpy as np
        from repro.streaming import StreamingKMeans
        from repro.data import PointStream
        mesh = jax.make_mesh((8,), ("data",))
        # 997 % 8 != 0 -> every batch pads
        stream = PointStream(shard_size=997, n_shards=4, n_dims=16, k=8,
                             seed=3)
        sk_l = StreamingKMeans(8, seed=5)
        sk_d = StreamingKMeans(8, seed=5, mesh=mesh)
        sk_l.fit_stream(stream, epochs=3)
        sk_d.fit_stream(stream, epochs=3)
        assert sk_d.stats_.sharded_batches == sk_d.stats_.batches > 0
        assert sk_d.stats_.cache_hits == sk_l.stats_.cache_hits > 0
        # the psum'd EMA differs from the local one by summation-order
        # rounding, so margin-riding filter decisions may flip: evals
        # agree to ~1%, effective counts to a few points, the total
        # effective mass exactly
        el, ed = sk_l.stats_.distance_evals, sk_d.stats_.distance_evals
        assert abs(el - ed) <= 0.02 * el, (el, ed)
        assert float(sk_d.counts_.sum()) == float(sk_l.counts_.sum())
        np.testing.assert_allclose(sk_d.counts_, sk_l.counts_, atol=8)
        np.testing.assert_allclose(sk_d.cluster_centers_,
                                   sk_l.cluster_centers_, atol=1e-3)
        full = np.concatenate([stream.shard(s) for s in range(4)], 0)
        i_l, i_d = sk_l.inertia_of(full), sk_d.inertia_of(full)
        assert abs(i_l - i_d) <= 1e-4 * max(i_l, 1.0)
        # the PrefetchingLoader/global_batch protocol drives the same
        # sharded step
        sk_g = StreamingKMeans(8, seed=5, mesh=mesh)
        sk_g.fit_stream([stream.global_batch(s) for s in range(4)])
        assert sk_g.stats_.sharded_batches == 4
        print("SHARDED-STREAM-OK")
    """)


def test_sharded_fit_adopts_tuned_shard_config():
    """make_fit_sharded(tune=): a tuned entry stored under the
    shard-count signature steers the compact body's capacities, and the
    result stays exact (tuning is wall-clock-only, also in the
    distributed engine)."""
    _run("""
        import os, jax, jax.numpy as jnp, numpy as np
        os.environ["REPRO_KMEANS_TUNE_CACHE"] = "/tmp/dist_tune.json"
        import repro.tune as tune
        tune.set_default_cache(None)
        from repro.core import distributed_yinyang, kmeans_plusplus
        from repro.core.engine import EngineConfig
        from repro.data import make_points
        pts_np, _, _ = make_points(4096, 16, 24, seed=0)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 24)
        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(max_iters=30, tol=1e-5)
        r_ref = distributed_yinyang(pts, init, mesh, tune="off", **kw)
        # per-shard n = 512; store a deliberately odd sharded config
        cfg = EngineConfig(min_cap=64, chunk=1024, down_n=4,
                           refresh_in_pass=True)
        sig = tune.signature(512, 24, 16, shards=8)
        assert sig.endswith("|s8")
        tune.default_cache().store(sig, cfg, ms=1.0)
        assert tune.lookup(n=512, k=24, d=16, shards=8) == cfg
        r_tuned = distributed_yinyang(pts, init, mesh, tune="auto", **kw)
        assert np.array_equal(np.asarray(r_ref.assignments),
                              np.asarray(r_tuned.assignments))
        np.testing.assert_allclose(float(r_ref.inertia),
                                   float(r_tuned.inertia), rtol=1e-6)
        print("SHARD-TUNE-OK")
    """)


def test_sharded_weighted_parity():
    """sample_weight through the unified sharded drivers: uniform
    weights are bit-identical to the unweighted fit (dense AND
    compact), and a non-uniform weighting matches the single-device
    weighted engine bit-for-bit — one weight implementation behind
    every reducer."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed_yinyang, engine_fit, \\
            kmeans_plusplus
        from repro.data import make_points
        pts_np, _, _ = make_points(4096, 16, 24, seed=0)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 24)
        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(max_iters=40, tol=1e-5)

        ones = jnp.ones((4096,), jnp.float32)
        for backend in ("dense", "compact"):
            r0 = distributed_yinyang(pts, init, mesh, backend=backend,
                                     **kw)
            r1 = distributed_yinyang(pts, init, mesh, backend=backend,
                                     sample_weight=ones, **kw)
            assert np.array_equal(np.asarray(r0.assignments),
                                  np.asarray(r1.assignments)), backend
            assert float(r0.inertia) == float(r1.inertia), backend
            assert int(r0.n_iters) == int(r1.n_iters), backend

        w = jnp.asarray(np.random.default_rng(0).integers(
            1, 4, size=4096).astype(np.float32))
        r_d = distributed_yinyang(pts, init, mesh, backend="compact",
                                  sample_weight=w, **kw)
        r_s = engine_fit(pts, init, backend="compact", tune="off",
                         sample_weight=w, **kw)
        assert np.array_equal(np.asarray(r_d.assignments),
                              np.asarray(r_s.assignments))
        np.testing.assert_allclose(float(r_d.inertia),
                                   float(r_s.inertia), rtol=1e-5)
        # uneven N + weights: pad rows get weight 0 and drop out
        pts_u = pts[:4001]
        init_u = kmeans_plusplus(jax.random.PRNGKey(2), pts_u, 24)
        r_du = distributed_yinyang(pts_u, init_u, mesh,
                                   backend="compact",
                                   sample_weight=w[:4001], **kw)
        r_su = engine_fit(pts_u, init_u, backend="compact", tune="off",
                          sample_weight=w[:4001], **kw)
        assert np.array_equal(np.asarray(r_du.assignments),
                              np.asarray(r_su.assignments))
        # weighted sharded streaming: uniform weights == unweighted.
        # The first batch seeds the cold start, and explicit weights
        # route it through the weighted k-means++ sampler (a different
        # program than the unweighted one) — feed it unweighted to
        # BOTH so the comparison holds seeding fixed and exercises the
        # weighted EMA steps.
        from repro.streaming import StreamingKMeans
        from repro.data import PointStream
        stream = PointStream(shard_size=997, n_shards=4, n_dims=16,
                             k=8, seed=3)
        sk_u = StreamingKMeans(8, seed=5, mesh=mesh)
        sk_w = StreamingKMeans(8, seed=5, mesh=mesh)
        for step, (sid, b) in enumerate(stream.batches(2)):
            sk_u.partial_fit(b, shard_id=sid)
            sk_w.partial_fit(b, shard_id=sid,
                             sample_weight=None if step == 0 else
                             np.ones(len(b), np.float32))
        np.testing.assert_array_equal(sk_u.cluster_centers_,
                                      sk_w.cluster_centers_)
        assert float(sk_u.counts_.sum()) == float(sk_w.counts_.sum())
        print("WEIGHTED-SHARDED-OK")
    """)


def test_sharded_autotune_measures_through_the_sharded_driver():
    """tune.autotune(shards=S) with no injected measure drives the
    REAL distributed_yinyang under shard_map (the ROADMAP remainder:
    |sS signatures from sharded measurement, not single-device
    fallback) — and the stored winner steers a subsequent
    distributed_yinyang(tune='auto') without changing its result."""
    _run("""
        import os, jax, jax.numpy as jnp, numpy as np
        os.environ["REPRO_KMEANS_TUNE_CACHE"] = "/tmp/dist_tune_m.json"
        import repro.tune as tune
        tune.set_default_cache(None)
        tune.default_cache().clear()
        from repro.core import distributed_yinyang, kmeans_plusplus
        from repro.data import make_points
        pts_np, _, _ = make_points(4096, 8, 16, seed=1)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 16)
        # one shard's worth (512 points), measured over 8 real devices
        cfg = tune.autotune(pts[:512], init, n_groups=2, max_iters=15,
                            shards=8, max_rounds=1, max_measurements=5,
                            repeats=1)
        sig = tune.signature(512, 16, 8, shards=8)
        assert sig.endswith("|s8")
        assert tune.default_cache().lookup(sig) == cfg
        assert cfg.backend == "compact"   # no Lloyd grid on sharded keys
        entry = tune.default_cache().entry(sig)
        assert entry["measured"] >= 1 and entry["ms"] > 0
        assert "lloyd_ms" not in entry
        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(max_iters=30, tol=1e-5)
        r_off = distributed_yinyang(pts, init, mesh, tune="off", **kw)
        r_tuned = distributed_yinyang(pts, init, mesh, tune="auto", **kw)
        assert np.array_equal(np.asarray(r_off.assignments),
                              np.asarray(r_tuned.assignments))
        print("SHARDED-MEASURE-OK")
    """)


def test_distributed_kmeans_matches_single_device():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import yinyang, distributed_yinyang, kmeans_plusplus
        from repro.data import make_points
        pts_np, _, _ = make_points(4096, 16, 24, seed=0)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 24)
        mesh = jax.make_mesh((8,), ("data",))
        r_d = distributed_yinyang(pts, init, mesh, axes=("data",),
                                  max_iters=40, tol=1e-5)
        r_s = yinyang(pts, init, max_iters=40, tol=1e-5)
        np.testing.assert_allclose(np.asarray(r_d.centroids),
                                   np.asarray(r_s.centroids), atol=1e-3)
        np.testing.assert_allclose(float(r_d.inertia), float(r_s.inertia),
                                   rtol=1e-4)
        print("DIST-KMEANS-OK")
    """)


def test_distributed_kmeans_compressed_psum_converges():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed_yinyang, yinyang, kmeans_plusplus
        from repro.data import make_points
        pts_np, _, _ = make_points(4096, 8, 16, seed=2)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 16)
        mesh = jax.make_mesh((8,), ("data",))
        r_c = distributed_yinyang(pts, init, mesh, compress=True,
                                  max_iters=40, tol=1e-5)
        r_s = yinyang(pts, init, max_iters=40, tol=1e-5)
        # int8 psum is approximate: inertia within 1%
        assert abs(float(r_c.inertia) - float(r_s.inertia)) \
            <= 0.01 * float(r_s.inertia)
        print("COMPRESSED-OK")
    """)


def test_sharded_train_step_runs_and_matches_unsharded():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.train.steps import init_train_state, make_train_step
        from repro.launch.sharding import (train_state_pspecs, batch_pspecs,
                                           named)
        cfg = get_config("qwen2-7b").reduced()
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        step = make_train_step(cfg)
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2),
                                              (8, 32), 0, cfg.vocab)}
        # unsharded reference
        _, m_ref = jax.jit(step)(state, batch)
        with mesh:
            st_sh = named(mesh, train_state_pspecs(cfg))
            b_sh = named(mesh, batch_pspecs(cfg, mesh))
            state_s = jax.device_put(state, st_sh)
            batch_s = jax.device_put(batch, b_sh)
            _, m_sh = jax.jit(step, in_shardings=(st_sh, b_sh),
                              out_shardings=(st_sh, None))(state_s, batch_s)
        np.testing.assert_allclose(float(m_ref["loss"]),
                                   float(m_sh["loss"]), rtol=2e-3)
        print("SHARDED-TRAIN-OK")
    """)


def test_elastic_restore_to_different_mesh():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.train.steps import init_train_state
        from repro.launch.sharding import train_state_pspecs, named
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        import tempfile
        cfg = get_config("phi4-mini-3.8b").reduced()
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        mesh_a = jax.make_mesh((8, 1), ("data", "model"))
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        with tempfile.TemporaryDirectory() as d:
            state_a = jax.device_put(state, named(mesh_a,
                                                  train_state_pspecs(cfg)))
            save_checkpoint(d, 1, state_a)
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            restored, step = restore_checkpoint(
                d, like, shardings=named(mesh_b, train_state_pspecs(cfg)))
            for a, b in zip(jax.tree.leaves(state_a),
                            jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC-OK")
    """)


def test_reduced_dryrun_lowers_on_8_devices():
    """The dry-run machinery itself (lower+compile+cost) on a reduced
    config and a small mesh — fast proxy for the production sweep."""
    _run("""
        import jax
        from repro.configs import get_config
        from repro.launch.sharding import (train_state_pspecs, batch_pspecs,
                                           named)
        from repro.train.steps import make_train_step, init_train_state
        import functools, jax.numpy as jnp
        cfg = get_config("hymba-1.5b").reduced()
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        step = make_train_step(cfg)
        state = jax.eval_shape(functools.partial(init_train_state, cfg=cfg),
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(named(mesh, train_state_pspecs(cfg)),
                              named(mesh, batch_pspecs(cfg, mesh))),
                out_shardings=(named(mesh, train_state_pspecs(cfg)), None),
            ).lower(state, batch)
            compiled = lowered.compile()
            assert compiled.cost_analysis().get("flops", 0) > 0
        print("DRYRUN-8DEV-OK")
    """)

def test_distributed_stats_rings_skew_and_watchdog():
    """Observability under real sharding: per-shard rings survive the
    shard_map (one (R, C) ring per shard), the global evals invariant
    reconciles exactly against the psum'd EvalCount, the skew gauge
    reflects a deliberately imbalanced shard (uniform noise on shard 0
    -> it does several times the median work -> the StragglerWatchdog
    flags exactly that shard), and obs on/off stays bit-identical."""
    _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed_yinyang, kmeans_plusplus
        from repro.data import make_points
        from repro.obs import MetricsRegistry
        from repro.obs.ring import COL_EVALS
        from repro.runtime.fault_tolerance import StragglerWatchdog

        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(n_groups=6, max_iters=30, tol=1e-5, backend="compact")

        # balanced fit first: parity + invariant + serializable stats
        pts_np, _, _ = make_points(4096, 16, 24, seed=0)
        pts = jnp.asarray(pts_np)
        init = kmeans_plusplus(jax.random.PRNGKey(1), pts, 24)
        r_off = distributed_yinyang(pts, init, mesh, **kw)
        reg = MetricsRegistry()
        r_on, st = distributed_yinyang(pts, init, mesh,
                                       return_stats=True, obs=reg, **kw)
        assert np.array_equal(np.asarray(r_off.assignments),
                              np.asarray(r_on.assignments))
        assert float(r_off.inertia) == float(r_on.inertia)
        assert st.shard_rings.shape[0] == 8
        assert st.ring.shape[0] == int(r_on.n_iters) + 1
        total = st.init_evals + float(np.sum(st.ring[:, COL_EVALS]))
        assert total == float(r_on.distance_evals), (total,
            float(r_on.distance_evals))
        json.dumps(st.to_dict())
        assert [e for e in reg.events if e["event"] == "distributed_fit"]

        # imbalanced fit: shard 0 = structureless uniform noise (its
        # bounds never prune -> far more evals than the median shard)
        rng = np.random.default_rng(7)
        clustered, _, _ = make_points(3584, 16, 24, seed=4,
                                      cluster_std=0.3)
        noise = rng.uniform(-20, 20, size=(512, 16)).astype(np.float32)
        pts = jnp.asarray(np.concatenate([noise, clustered], axis=0))
        init = kmeans_plusplus(jax.random.PRNGKey(2), pts, 24)
        wd = StragglerWatchdog(threshold=1.6)
        _, st = distributed_yinyang(pts, init, mesh, return_stats=True,
                                    watchdog=wd, **kw)
        assert float(np.max(st.shard_skew)) > 1.5, st.shard_skew
        assert wd.events, "noise shard never flagged"
        assert all(e["shard"] == 0 for e in wd.events), wd.events
        print("DIST-OBS-OK")
    """)
