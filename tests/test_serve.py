"""The serving subsystem: swap consistency, bucket discipline, drift-
gated table reuse, the serve knob family, and engine lifecycle."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pairwise_sq_dists
from repro.core import engine as _engine
from repro.core.distances import row_norms_sq
from repro.obs import MetricsRegistry
from repro.serve import CentroidIndex, ServeEngine
from repro.tune import (ServeConfig, TuneCache, autotune_serve,
                        lookup_serve, serve_signature)


def _dense_labels(q, centroids):
    return np.asarray(jnp.argmin(
        pairwise_sq_dists(jnp.asarray(q), jnp.asarray(centroids)), axis=1))


def _mk(n, d, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


# -- swap consistency: the acceptance criterion --------------------------


def test_swap_consistency_exactly_one_epoch():
    """Under a concurrent publisher, every response's labels must match
    the dense oracle of ITS OWN epoch exactly — a batch that mixed two
    epochs could not satisfy any single epoch's oracle (the centroid
    sets are independent draws, so their label maps differ)."""
    d, k = 8, 16
    q = _mk(4096, d, 0)
    pub_rng = np.random.default_rng(1)
    c0 = _mk(k, d, 2)
    epoch_centroids = {1: c0}
    idx = CentroidIndex(c0)
    stop = threading.Event()

    def publisher():
        while not stop.is_set():
            c = pub_rng.standard_normal((k, d)).astype(np.float32)
            ep = idx.publish(c)
            epoch_centroids[ep] = c
            time.sleep(0.001)

    cfg = ServeConfig(min_bucket=64, max_batch=1024)
    req_rng = np.random.default_rng(3)
    results = []
    with ServeEngine(idx, config=cfg, tune="off") as eng:
        eng.assign(q[:64])              # compile before the clock
        t = threading.Thread(target=publisher)
        t.start()
        try:
            for _ in range(100):
                m = int(req_rng.integers(16, 600))
                lo = int(req_rng.integers(0, q.shape[0] - m))
                results.append((lo, m, eng.assign(q[lo:lo + m])))
                time.sleep(0.001)
        finally:
            stop.set()
            t.join()

    epochs = set()
    for lo, m, (labels, epoch) in results:
        assert labels.shape == (m,)
        ref = _dense_labels(q[lo:lo + m], epoch_centroids[epoch])
        assert np.array_equal(labels, ref), \
            f"labels mixed epochs (claimed epoch {epoch})"
        epochs.add(epoch)
    # the publisher really swapped mid-traffic, so the parity above
    # exercised more than one epoch
    assert len(epochs) > 1


# -- bucket lattice: ragged traffic must not recompile --------------------


def test_bucket_reuse_no_recompile():
    # distinctive (d, k): the serve jits are module-level, so their
    # program cache is shared across tests — unique shapes make the
    # compile-count deltas below attributable to THIS test's buckets
    d, k = 12, 20
    q = _mk(1024, d, 0)
    idx = CentroidIndex(_mk(k, d, 1))
    cfg = ServeConfig(min_bucket=256, max_batch=1024)
    with ServeEngine(idx, config=cfg, tune="off") as eng:
        eng.assign(q[:300])             # bucket 512: compile
        (fn,) = eng._assigns.values()
        n0 = fn.cache_size()
        for m in (257, 400, 511, 512):  # all land in bucket 512
            labels, _ = eng.assign(q[:m])
            assert labels.shape == (m,)
        assert fn.cache_size() == n0, "ragged sizes recompiled"
        eng.assign(q[:600])             # bucket 1024: one new program
        assert fn.cache_size() == n0 + 1


# -- drift-gated table rebuild vs reuse -----------------------------------


def test_index_reuses_tables_under_drift_threshold():
    k, d = 16, 8
    c = _mk(k, d, 0)
    idx = CentroidIndex(rebuild_threshold=0.05)
    # the first publish must carry drift info too — it sets the
    # baseline the reuse decision is measured against
    idx.publish(c, cum_drift=np.zeros(k))
    s1 = idx.acquire()
    assert (idx.publishes, idx.rebuilds, idx.reuses) == (1, 1, 0)

    # tiny cumulative drift since that baseline: tables REUSED (same
    # objects)
    drift = np.full(k, 1e-4)
    idx.publish(c + 1e-4, cum_drift=drift)
    s2 = idx.acquire()
    assert s2.epoch == 2 and s2.tables_epoch == s1.epoch
    assert s2.members is s1.members and s2.groups is s1.groups
    assert idx.reuses == 1

    # large drift: rebuild, tables stamped with the new epoch
    idx.publish(c * 3.0, cum_drift=drift + 100.0)
    s3 = idx.acquire()
    assert s3.tables_epoch == s3.epoch == 3
    assert idx.rebuilds == 2

    # no drift information -> always rebuild (the safe default)
    idx.publish(c)
    assert idx.rebuilds == 3
    # force_rebuild wins even under tiny drift
    idx.publish(c, cum_drift=np.zeros(k), force_rebuild=True)
    assert idx.rebuilds == 4


def test_index_acquire_before_publish_raises():
    idx = CentroidIndex()
    assert not idx.ready
    with pytest.raises(RuntimeError):
        idx.acquire()


# -- every serve backend is exact ----------------------------------------


@pytest.mark.parametrize("backend", ["fused", "grouped", "pallas"])
def test_make_serve_assign_backends_exact(backend):
    k, d = 32, 8
    q = _mk(512, d, 0)
    centroids = _mk(k, d, 1)
    cj = jnp.asarray(centroids)
    c2 = row_norms_sq(cj)
    groups, members, gsize = _engine.build_assign_tables(cj)
    fn = _engine.make_serve_assign((k, int(gsize.shape[0])),
                                   backend=backend, chunk=256,
                                   interpret=True)
    labels = np.asarray(fn(jnp.asarray(q), cj, c2, groups, members,
                           gsize))
    assert np.array_equal(labels, _dense_labels(q, centroids))


def test_make_serve_assign_unknown_backend():
    with pytest.raises(ValueError):
        _engine.make_serve_assign((8, 2), backend="nope")


# -- engine lifecycle -----------------------------------------------------


def test_engine_empty_request():
    idx = CentroidIndex(_mk(4, 8, 0))
    with ServeEngine(idx, config=ServeConfig(), tune="off") as eng:
        labels, epoch = eng.assign(np.zeros((0, 8), np.float32))
        assert labels.shape == (0,) and epoch == 1


def test_engine_jumbo_request_split_and_exact():
    """A request larger than max_batch is split internally; the caller
    sees one future with the full concatenated labels."""
    d, k = 8, 16
    q = _mk(1300, d, 0)
    centroids = _mk(k, d, 1)
    idx = CentroidIndex(centroids)
    cfg = ServeConfig(min_bucket=64, max_batch=512)
    with ServeEngine(idx, config=cfg, tune="off") as eng:
        labels, epoch = eng.assign(q)
        assert labels.shape == (1300,) and epoch == 1
        assert np.array_equal(labels, _dense_labels(q, centroids))


def test_engine_coalescing_never_overflows_max_batch():
    """Many ragged requests queued at once: coalescing stops before a
    request that would overflow max_batch (it opens the next batch), so
    every request is answered exactly and in submission order."""
    d, k = 8, 16
    q = _mk(4096, d, 0)
    centroids = _mk(k, d, 1)
    idx = CentroidIndex(centroids)
    cfg = ServeConfig(min_bucket=64, max_batch=512)
    rng = np.random.default_rng(2)
    spans = [(int(rng.integers(0, 3500)), int(rng.integers(100, 500)))
             for _ in range(40)]
    with ServeEngine(idx, config=cfg, tune="off") as eng:
        futs = [eng.submit(q[lo:lo + m]) for lo, m in spans]
        results = [f.result(timeout=60) for f in futs]
        assert eng.points == sum(m for _, m in spans)
        assert eng.batches < len(spans)     # requests were coalesced
    for (lo, m), (labels, _) in zip(spans, results):
        assert np.array_equal(labels, _dense_labels(q[lo:lo + m],
                                                    centroids))


def test_engine_device_resident_submit_exact():
    """A device-resident f32 jax.Array block skips host staging (the
    exact-fit path feeds it straight to the jitted assign) and yields
    the same labels as the numpy route."""
    d, k = 8, 16
    q = _mk(512, d, 3)
    centroids = _mk(k, d, 1)
    idx = CentroidIndex(centroids)
    cfg = ServeConfig(min_bucket=64, max_batch=512)
    with ServeEngine(idx, config=cfg, tune="off") as eng:
        labels_np, _ = eng.assign(q)
        labels_dev, epoch = eng.assign(jnp.asarray(q))
        assert epoch == 1
        assert np.array_equal(labels_dev, labels_np)
        assert np.array_equal(labels_dev, _dense_labels(q, centroids))
        # jumbo device-resident blocks split on device, same contract
        big = jnp.asarray(_mk(1300, d, 4))
        labels, _ = eng.assign(big)
        assert labels.shape == (1300,)
        assert np.array_equal(labels,
                              _dense_labels(np.asarray(big), centroids))
        # non-f32 device input falls back to the host coercion path
        labels16, _ = eng.assign(jnp.asarray(q, dtype=jnp.float16))
        assert labels16.shape == (512,)


def test_engine_submit_requires_running():
    idx = CentroidIndex(_mk(4, 8, 0))
    eng = ServeEngine(idx, config=ServeConfig(), tune="off")
    with pytest.raises(RuntimeError):
        eng.submit(np.zeros((4, 8), np.float32))


def test_engine_stop_before_publish_fails_pending():
    idx = CentroidIndex()                 # nothing ever published
    eng = ServeEngine(idx, config=ServeConfig(), tune="off").start()
    fut = eng.submit(np.zeros((4, 8), np.float32))
    eng.stop()
    with pytest.raises(RuntimeError):
        fut.result(timeout=30)


def test_engine_stop_before_publish_fails_split_jumbo():
    """A jumbo (split) request must also fail — not hang — when the
    engine stops with no published centroids: the part futures carry
    the exception, and the split must propagate it to the user future
    (``f.result()`` inside ``add_done_callback`` would be swallowed)."""
    idx = CentroidIndex()
    cfg = ServeConfig(min_bucket=64, max_batch=128)
    eng = ServeEngine(idx, config=cfg, tune="off").start()
    fut = eng.submit(_mk(300, 8, 0))      # 3 parts
    eng.stop()
    with pytest.raises(RuntimeError):
        fut.result(timeout=30)


def test_engine_submit_rejects_wrong_feature_dim():
    """A wrong-D block must be rejected synchronously at submit — on
    the serve thread it would fail mid-batch (and before the loop was
    hardened, kill the thread)."""
    idx = CentroidIndex(_mk(8, 16, 0))
    with ServeEngine(idx, config=ServeConfig(), tune="off") as eng:
        with pytest.raises(ValueError, match="feature dim"):
            eng.submit(_mk(4, 8, 1))
        labels, _ = eng.assign(_mk(4, 16, 2))   # engine still serves
        assert labels.shape == (4,)


def test_engine_thread_survives_batch_error():
    """A backend failure inside one batch must fail THAT batch's
    futures and leave the serve thread alive for the next request —
    not die silently and hang every later submit."""
    d, k = 8, 16
    idx = CentroidIndex(_mk(k, d, 0))
    cfg = ServeConfig(min_bucket=64, max_batch=512)
    with ServeEngine(idx, config=cfg, tune="off") as eng:
        orig = eng._resolve_assign

        def boom(*a, **kw):
            raise RuntimeError("injected backend failure")

        eng._resolve_assign = boom
        with pytest.raises(RuntimeError, match="injected"):
            eng.submit(_mk(16, d, 1)).result(timeout=30)
        eng._resolve_assign = orig
        labels, _ = eng.assign(_mk(16, d, 2))
        assert labels.shape == (16,)


def test_engine_client_device_array_never_donated(monkeypatch):
    """The exact-fit fast path hands the CLIENT'S jax.Array to the
    jitted assign; off-CPU it must resolve the non-donating variant
    (donation would invalidate the caller's buffer in place), while
    engine-staged numpy batches keep donation. Simulated off-CPU via
    the backend probe; on real CPU donation is a no-op either way."""
    d, k = 8, 16
    q = _mk(512, d, 3)
    centroids = _mk(k, d, 1)
    idx = CentroidIndex(centroids)
    cfg = ServeConfig(min_bucket=64, max_batch=512)
    with ServeEngine(idx, config=cfg, tune="off") as eng:
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        qd = jnp.asarray(q)
        labels_dev, _ = eng.assign(qd)          # exact-fit client array
        labels_np, _ = eng.assign(q[:300])      # staged numpy batch
        assert {key[2] for key in eng._assigns} == {False, True}
        # the client's buffer stays usable after serving
        assert np.array_equal(np.asarray(qd), q)
        assert np.array_equal(labels_dev, _dense_labels(q, centroids))
        assert np.array_equal(labels_np,
                              _dense_labels(q[:300], centroids))


def test_engine_config_not_pinned_before_first_publish(monkeypatch):
    """A submit racing the first publish must not permanently cache the
    default config: the tuned ``serve|`` entry (which needs the
    snapshot's k/d) must still win once centroids exist."""
    import repro.serve.engine as se
    tuned = ServeConfig(max_batch=2048, chunk=512)
    monkeypatch.setattr(se, "lookup_serve", lambda **kw: tuned)
    idx = CentroidIndex()
    eng = ServeEngine(idx, tune="on")
    assert eng._config() == se.DEFAULT_SERVE_CONFIG
    assert eng._cfg is None               # fallback was NOT memoized
    idx.publish(_mk(8, 8, 0))
    assert eng._config() == tuned


def test_engine_counts_and_metrics():
    d, k = 8, 16
    q = _mk(2048, d, 0)
    reg = MetricsRegistry()
    idx = CentroidIndex(_mk(k, d, 1), obs=reg)
    cfg = ServeConfig(min_bucket=256, max_batch=1024)
    with ServeEngine(idx, config=cfg, tune="off", obs=reg) as eng:
        eng.assign(q[:300])
        eng.assign(q[:900])
        idx.publish(_mk(k, d, 2))
        _, epoch = eng.assign(q[:100])
        assert epoch == 2
        assert eng.batches == 3 and eng.points == 1300
        assert eng.epoch_swaps == 1
    text = reg.to_prometheus()
    for name in ("serve_batches_total", "serve_points_total",
                 "serve_epoch_swaps_total", "serve_batch_fill",
                 "serve_latency_seconds", "serve_publishes_total",
                 "serve_epoch"):
        assert name in text, f"missing metric {name}"


# -- the serve knob family ------------------------------------------------


def test_serve_config_roundtrip_and_tolerance():
    cfg = ServeConfig(backend="grouped", chunk=512).replace(max_batch=2048)
    assert ServeConfig.from_dict(cfg.to_dict()) == cfg
    # unknown keys from a newer writer are ignored, not fatal
    assert ServeConfig.from_dict(
        {**cfg.to_dict(), "future_knob": 1}) == cfg


def test_serve_signature_shape():
    sig = serve_signature(64, 32, platform="cpu")
    assert sig == "serve|cpu|k64|d32"


def test_autotune_serve_stores_and_lookup_finds(tmp_path):
    cache = TuneCache(str(tmp_path / "tc.json"))
    assert lookup_serve(k=8, d=4, cache=cache) is None
    cfg = autotune_serve(k=8, d=4, backends=["fused"], chunks=(256,),
                         max_batch=512, repeats=1, cache=cache)
    assert cfg.backend == "fused" and cfg.chunk == 256
    got = lookup_serve(k=8, d=4, cache=cache)
    assert got == cfg
