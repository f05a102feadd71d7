"""Resilient streaming fits: checkpoint/restore-replay chaos suite.

The contract under test (see ``repro/streaming/resilient.py`` and
``docs/fault_tolerance.md``): a streaming fit that crashes anywhere —
between batches, mid-batch with torn host state, before the first
checkpoint, or onto a corrupt checkpoint — restores and REPLAYS the
deterministic ``(seed, shard)`` stream to centroids / counts / drift
ledger BIT-IDENTICAL to an uninterrupted run. Elastic restores into a
grown/shrunk mesh keep every cached bound valid and land on the same
clustering up to psum re-association (inertia parity).

Fast single-device roundtrip/resume tests run in tier 1; the
failure-injection and forced-multi-device elastic tests carry the
``chaos`` marker and run in CI's chaos lane.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.data import PointStream
from repro.runtime.fault_tolerance import FailureInjector, InjectedFailure
from repro.streaming import StreamingKMeans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stream(seed=7, n_shards=4):
    return PointStream(shard_size=256, n_shards=n_shards, n_dims=8, k=8,
                       seed=seed)


def _assert_stream_state_equal(a: StreamingKMeans, b: StreamingKMeans):
    np.testing.assert_array_equal(a.cluster_centers_, b.cluster_centers_)
    np.testing.assert_array_equal(a.counts_, b.counts_)
    np.testing.assert_array_equal(a._ledger.centroid, b._ledger.centroid)
    np.testing.assert_array_equal(a._ledger.group, b._ledger.group)


# -- tier-1: save/restore roundtrip and resume -----------------------------

def test_save_restore_roundtrip_full_state(tmp_path):
    """Every piece of stream state survives the checkpoint: bound
    cache (entries, LRU order, scalars), float64 ledger (bit-exact —
    it must never transit a device), reseed reservoir, stats, tuned
    engine config. A restored estimator is indistinguishable going
    forward: the next batch produces bit-identical state."""
    stream = _stream()
    skm = StreamingKMeans(8, seed=1).fit_stream(stream, epochs=2)
    skm.save(tmp_path, step=8)
    got, step = StreamingKMeans.restore(tmp_path)
    assert step == 8
    _assert_stream_state_equal(skm, got)
    np.testing.assert_array_equal(skm._since_hit, got._since_hit)
    np.testing.assert_array_equal(skm._groups_np, got._groups_np)
    np.testing.assert_array_equal(skm.labels_, got.labels_)
    assert got._ledger.centroid.dtype == np.float64
    d1, d2 = skm.stats_.to_dict(), got.stats_.to_dict()
    for key in ("ckpt_saves", "restores"):   # legitimately differ
        d1.pop(key), d2.pop(key)
    assert d1 == d2
    assert skm.ewa_inertia_ == got.ewa_inertia_
    assert (skm.min_bucket, skm.chunk, skm._ggf) == \
        (got.min_bucket, got.chunk, got._ggf)
    assert len(skm._far) == len(got._far)
    for (u1, p1), (u2, p2) in zip(skm._far, got._far):
        assert u1 == u2
        np.testing.assert_array_equal(p1, p2)
    assert list(skm._cache._d.keys()) == list(got._cache._d.keys())
    for sid in skm._cache._d:
        e1, e2 = skm._cache._d[sid], got._cache._d[sid]
        np.testing.assert_array_equal(e1.assignments, e2.assignments)
        np.testing.assert_array_equal(e1.ub, e2.ub)
        np.testing.assert_array_equal(e1.lb, e2.lb)
        np.testing.assert_array_equal(e1.ub_off, e2.ub_off)
        np.testing.assert_array_equal(e1.gdrift_snap, e2.gdrift_snap)
        assert (e1.gmax, e1.ub_scale) == (e2.gmax, e2.ub_scale)
    # the restored estimator continues bit-identically
    skm.partial_fit(stream.shard(0), shard_id=0)
    got.partial_fit(stream.shard(0), shard_id=0)
    _assert_stream_state_equal(skm, got)
    assert skm.stats_.cache_hits == got.stats_.cache_hits


def test_save_requires_initialized(tmp_path):
    from repro.core import NotFittedError
    with pytest.raises(NotFittedError):
        StreamingKMeans(4).save(tmp_path, step=0)


def test_restore_rejects_wrong_format(tmp_path):
    from repro.checkpoint import save_checkpoint
    save_checkpoint(tmp_path, 1, [np.zeros((3,))], meta={"format": "other"})
    with pytest.raises(ValueError):
        StreamingKMeans.restore(tmp_path)


def test_resilient_requires_global_batch_source(tmp_path):
    with pytest.raises(ValueError):
        StreamingKMeans(4).fit_stream(
            [np.zeros((8, 3), np.float32)], resilient=True,
            ckpt_dir=tmp_path)
    with pytest.raises(ValueError):
        StreamingKMeans(4).fit_stream(_stream(), resilient=True)


def test_resume_across_runs_bit_exact(tmp_path):
    """Stop after 2 epochs (terminal checkpoint), resume a FRESH
    estimator for 4 — bit-identical to 4 uninterrupted epochs. This is
    the planned-restart path (the preemption story without the
    failure)."""
    stream = _stream(seed=9)
    sk_u = StreamingKMeans(8, seed=3).fit_stream(stream, epochs=4)

    sk_a = StreamingKMeans(8, seed=3)
    sk_a.fit_stream(stream, epochs=2, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=3)
    sk_b = StreamingKMeans(8, seed=3)   # new process, no memory of sk_a
    sk_b.fit_stream(stream, epochs=4, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=3)
    _assert_stream_state_equal(sk_u, sk_b)
    assert sk_b.stats_.restores == 1
    assert sk_b.stats_.replayed_batches == 0   # resumed, nothing replayed


def test_checkpointed_fit_bit_exact_with_plain_fit(tmp_path):
    """Checkpointing is a pure observer: a failure-free fit under
    ``resilient=True``, saving every 3 batches and at the end, is
    bit-identical to the plain fit."""
    stream = _stream(seed=4)
    sk_u = StreamingKMeans(8, seed=3).fit_stream(stream, epochs=2)
    sk_c = StreamingKMeans(8, seed=3)
    sk_c.fit_stream(stream, epochs=2, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=3)
    _assert_stream_state_equal(sk_u, sk_c)
    assert sk_c.stats_.ckpt_saves >= 3
    assert sk_c.stats_.restores == 0


def test_adopt_centroids_keeps_cached_bounds_valid():
    """Warm handover: adopted centroids enter the ledger as drift, so
    the stream continues on the old bound cache without violating a
    single triangle-inequality bound (finite, sane inertia)."""
    stream = _stream(seed=5)
    skm = StreamingKMeans(8, seed=2).fit_stream(stream, epochs=2)
    led_before = skm._ledger.centroid.copy()
    rng = np.random.default_rng(0)
    skm.adopt_centroids(skm.cluster_centers_
                        + rng.standard_normal((8, 8)).astype(np.float32))
    assert np.all(skm._ledger.centroid >= led_before)
    hits_before = skm.stats_.cache_hits
    skm.fit_stream(stream, epochs=1)
    assert skm.stats_.cache_hits > hits_before   # cache survived
    pts = np.concatenate([stream.shard(i) for i in range(4)])
    assert np.isfinite(skm.inertia_of(pts))


# -- chaos lane: failure injection -----------------------------------------

pytest_chaos = pytest.mark.chaos


@pytest_chaos
def test_restore_replay_bit_exact_after_crash(tmp_path):
    """The acceptance scenario: inject a failure mid-epoch, restore
    the async checkpoint, replay the deterministic stream — final
    centroids bit-identical to the uninterrupted run."""
    stream = _stream()
    sk_u = StreamingKMeans(8, seed=3).fit_stream(stream, epochs=3)
    inj = FailureInjector(fail_at=(7,))
    sk_r = StreamingKMeans(8, seed=3)
    sk_r.fit_stream(stream, epochs=3, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=3, injector=inj)
    assert inj.seen == {7}
    assert sk_r.stats_.restores == 1
    assert sk_r.stats_.replayed_batches >= 1
    _assert_stream_state_equal(sk_u, sk_r)


@pytest_chaos
def test_crash_mid_batch_torn_state_recovers(tmp_path):
    """Host crash MID-batch: the chaos hook fires after the device
    update landed but before the host commit (ledger/cache/stats), so
    the estimator is genuinely torn. Restore must discard the torn
    half-step and land bit-identical."""
    stream = _stream(seed=2)
    sk_u = StreamingKMeans(8, seed=1).fit_stream(stream, epochs=3)
    sk_r = StreamingKMeans(8, seed=1)
    fired = []

    def tear_once(est, sid):
        if est.stats_.batches == 8 and not fired:
            fired.append(sid)
            raise InjectedFailure("host died mid-batch")

    sk_r.chaos_hook = tear_once
    sk_r.fit_stream(stream, epochs=3, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=4)
    assert fired
    assert sk_r.stats_.restores == 1
    _assert_stream_state_equal(sk_u, sk_r)


@pytest_chaos
def test_failure_before_first_checkpoint_cold_restarts(tmp_path):
    """A stale/absent checkpoint directory: the failure lands before
    anything was saved (huge ckpt_every), so recovery is a cold
    restart replaying from step 0 — still bit-exact, because the cold
    start itself is (seed, shard)-deterministic."""
    stream = _stream(seed=4)
    sk_u = StreamingKMeans(8, seed=2).fit_stream(stream, epochs=2)
    inj = FailureInjector(fail_at=(5,))
    sk_r = StreamingKMeans(8, seed=2)
    sk_r.fit_stream(stream, epochs=2, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=1000, injector=inj)
    assert sk_r.stats_.restores == 1
    assert sk_r.stats_.replayed_batches == 5
    _assert_stream_state_equal(sk_u, sk_r)


@pytest_chaos
def test_corrupt_checkpoint_falls_back_and_replays(tmp_path):
    """Chaos on the STORAGE: the newest checkpoint is torn on disk.
    Recovery walks back to the previous complete save and replays the
    longer tail — bit-exact either way."""
    stream = _stream(seed=6)
    sk_u = StreamingKMeans(8, seed=5).fit_stream(stream, epochs=3)
    corrupted = []

    def corrupt_then_fail(est, sid):
        if est.stats_.batches == 9 and not corrupted:
            # tear the newest published step, then crash
            steps = sorted(p for p in os.listdir(tmp_path)
                           if p.startswith("step_"))
            with open(os.path.join(tmp_path, steps[-1], "shard_0.npz"),
                      "wb") as f:
                f.write(b"torn write")
            corrupted.append(steps[-1])
            raise InjectedFailure("crash onto corrupt checkpoint")

    sk_r = StreamingKMeans(8, seed=5)
    sk_r.chaos_hook = corrupt_then_fail
    sk_r.fit_stream(stream, epochs=3, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=3, async_ckpt=False)
    assert corrupted
    assert sk_r.stats_.restores == 1
    _assert_stream_state_equal(sk_u, sk_r)


@pytest_chaos
def test_shard_dropout_stream_keeps_going(tmp_path):
    """A shard's host drops out of the stream after a restore: the fit
    continues on the surviving shards (the lost shard's cached bounds
    just age in the LRU; its centroids keep living off other shards'
    points), stays finite, and reseeding patience is epoch-scaled so
    nothing is spuriously killed."""
    stream = _stream(seed=8)
    skm = StreamingKMeans(8, seed=1)
    skm.fit_stream(stream, epochs=2, resilient=True, ckpt_dir=tmp_path,
                   ckpt_every=4)
    got, step = StreamingKMeans.restore(tmp_path)
    assert step == 8
    surviving = [s for s in range(4) if s != 2]
    for epoch in range(2):
        for s in surviving:
            got.partial_fit(stream.shard(s), shard_id=s)
    pts = np.concatenate([stream.shard(i) for i in range(4)])
    assert np.isfinite(got.inertia_of(pts))
    assert got.stats_.batches == 8 + 6


@pytest_chaos
def test_multiple_failures_within_budget(tmp_path):
    stream = _stream(seed=12)
    sk_u = StreamingKMeans(8, seed=7).fit_stream(stream, epochs=4)
    inj = FailureInjector(fail_at=(3, 9, 13))
    sk_r = StreamingKMeans(8, seed=7)
    sk_r.fit_stream(stream, epochs=4, resilient=True, ckpt_dir=tmp_path,
                    ckpt_every=2, injector=inj, max_restarts=5)
    assert sk_r.stats_.restores == 3
    _assert_stream_state_equal(sk_u, sk_r)


@pytest_chaos
def test_restart_budget_exhausted_raises(tmp_path):
    stream = _stream(seed=1)
    inj = FailureInjector(fail_at=(2, 3, 4))
    with pytest.raises(InjectedFailure):
        StreamingKMeans(8, seed=1).fit_stream(
            stream, epochs=2, resilient=True, ckpt_dir=tmp_path,
            ckpt_every=2, injector=inj, max_restarts=2)


@pytest_chaos
def test_recovery_metrics_published(tmp_path):
    """ckpt_*/restore_*/replay_* observability: the registry sees the
    saves, the restore and the replayed batches, and the event log
    carries ckpt_save/restore events."""
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    stream = _stream(seed=3)
    inj = FailureInjector(fail_at=(7,))   # off the ckpt lattice: replay
    skm = StreamingKMeans(8, seed=2, obs=reg)
    skm.fit_stream(stream, epochs=2, resilient=True, ckpt_dir=tmp_path,
                   ckpt_every=2, injector=inj)
    m = reg.to_dict()
    assert m["ckpt_saves_total"] >= 2
    assert m["restore_total"] == 1
    assert m["replay_batches_total"] >= 1
    events = [e["event"] for e in reg.events]
    assert "ckpt_save" in events and "restore" in events


# -- chaos lane: elastic resize (forced multi-device subprocesses) ---------

def _run_forced(body: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=560)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    return out.stdout


@pytest_chaos
def test_elastic_grow_2_to_4(tmp_path):
    """Checkpoint under a 2-shard mesh, restore into a 4-shard mesh
    (and single-device): batches re-pad into the new lattice, cached
    bounds stay valid, and the final clustering matches the
    uninterrupted 2-shard run with inertia parity. Same-topology
    recovery stays bit-exact."""
    _run_forced(f"""
        import tempfile, numpy as np, jax
        from repro.core.distributed import make_mesh
        from repro.data import PointStream
        from repro.runtime.fault_tolerance import FailureInjector
        from repro.streaming import StreamingKMeans
        assert len(jax.devices()) == 4
        stream = PointStream(shard_size=256, n_shards=4, n_dims=8, k=8,
                             seed=11)
        pts = np.concatenate([stream.shard(i) for i in range(4)])
        mesh2 = make_mesh(2)

        sk_full = StreamingKMeans(8, seed=1, mesh=mesh2)
        sk_full.fit_stream(stream, epochs=3)
        ref = sk_full.inertia_of(pts)

        # same-topology crash recovery: bit-exact
        d = {str(tmp_path)!r}
        inj = FailureInjector(fail_at=(9,))
        sk_r = StreamingKMeans(8, seed=1, mesh=mesh2)
        sk_r.fit_stream(stream, epochs=3, resilient=True, ckpt_dir=d,
                        ckpt_every=4, injector=inj)
        assert np.array_equal(sk_full.cluster_centers_,
                              sk_r.cluster_centers_)
        assert np.array_equal(sk_full.counts_, sk_r.counts_)

        # elastic grow: the step-8 checkpoint re-pads into 4 shards
        sk_g, step = StreamingKMeans.restore(d, step=8, mesh=make_mesh(4))
        assert step == 8
        for s in range(8, 12):
            b = stream.global_batch(s)
            sk_g.partial_fit(b["points"], shard_id=b["shard_id"])
        got = sk_g.inertia_of(pts)
        assert abs(got - ref) / ref < 0.02, (got, ref)
        assert sk_g.stats_.cache_hits >= 8   # tail revisits hit the cache

        # and into a single device (mesh=None)
        sk_s, step = StreamingKMeans.restore(d, step=8)
        for s in range(8, 12):
            b = stream.global_batch(s)
            sk_s.partial_fit(b["points"], shard_id=b["shard_id"])
        got_s = sk_s.inertia_of(pts)
        assert abs(got_s - ref) / ref < 0.02, (got_s, ref)
        print("grow OK", ref, got, got_s)
    """)


@pytest_chaos
def test_elastic_shrink_4_to_2(tmp_path):
    """The preemption direction: checkpoint under 4 shards, lose two
    hosts, restore into a 2-shard mesh and finish — inertia parity
    with the uninterrupted 4-shard run."""
    _run_forced(f"""
        import numpy as np, jax
        from repro.core.distributed import make_mesh
        from repro.data import PointStream
        from repro.streaming import StreamingKMeans
        assert len(jax.devices()) == 4
        stream = PointStream(shard_size=256, n_shards=4, n_dims=8, k=8,
                             seed=13)
        pts = np.concatenate([stream.shard(i) for i in range(4)])
        mesh4 = make_mesh(4)

        sk_full = StreamingKMeans(8, seed=2, mesh=mesh4)
        sk_full.fit_stream(stream, epochs=3)
        ref = sk_full.inertia_of(pts)

        d = {str(tmp_path)!r}
        sk_a = StreamingKMeans(8, seed=2, mesh=mesh4)
        sk_a.fit_stream(stream, epochs=2, resilient=True, ckpt_dir=d,
                        ckpt_every=4)
        sk_b, step = StreamingKMeans.restore(d, mesh=make_mesh(2))
        assert step == 8
        for s in range(8, 12):
            b = stream.global_batch(s)
            sk_b.partial_fit(b["points"], shard_id=b["shard_id"])
        got = sk_b.inertia_of(pts)
        assert abs(got - ref) / ref < 0.02, (got, ref)
        print("shrink OK", ref, got)
    """)
