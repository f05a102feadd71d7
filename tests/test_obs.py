"""Observability layer: telemetry must be free and truthful.

The obs contract has two halves, both tested here:

* **Free** — turning the ring/metrics on changes NOTHING about the
  results: bit-identical assignments/inertia on every backend, and the
  zero-host-sync execution contract (``EngineStats.host_syncs``) is
  unchanged, because the ring rides the device loop carry and is
  drained exactly once at exit.
* **Truthful** — the ring's evals column reconciles EXACTLY with the
  engine's compensated ``EvalCount`` total (``init_evals +
  ring[:, COL_EVALS].sum() == distance_evals``, no tolerance), the
  epilogue row carries the true local inertia, and the shard-ring
  reductions (sum for additive counters, max for high-waters) are the
  arithmetic they claim.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, kmeans_plusplus
from repro.core.api import KMeans
from repro.data import make_points
from repro.obs import (MetricsRegistry, ObsConfig, add_ring_listener,
                       caps_from_ring, compile_count, default_registry,
                       normalize_obs, reduce_shard_rings,
                       remove_ring_listener, shard_skew, span,
                       summarize_ring)
from repro.obs.ring import (COL_EVALS, COL_INERTIA, COL_N_CAND,
                            N_COUNTERS, RING_COLUMNS)
from repro.runtime.fault_tolerance import StragglerWatchdog

BACKENDS = ["oracle", "compact", "pallas"]


def _dataset(n=1500, d=8, k=12, seed=0):
    pts, _, _ = make_points(n, d, k, seed=seed)
    pts = jnp.asarray(pts)
    init = kmeans_plusplus(jax.random.PRNGKey(seed + 1), pts, k)
    return pts, init


# -------------------------------------------------------------------------
# free: obs on == obs off, bit for bit, same host-sync count
# -------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_obs_bit_identical_and_host_syncs_unchanged(backend):
    pts, init = _dataset()
    kw = dict(n_groups=3, max_iters=40, tol=1e-5, backend=backend,
              interpret=True, tune="off", return_stats=True)
    r_off, s_off = engine.fit(pts, init, **kw)
    r_on, s_on = engine.fit(pts, init, obs=ObsConfig(
        registry=MetricsRegistry()), **kw)
    np.testing.assert_array_equal(np.asarray(r_off.assignments),
                                  np.asarray(r_on.assignments))
    np.testing.assert_array_equal(np.asarray(r_off.centroids),
                                  np.asarray(r_on.centroids))
    assert float(r_off.inertia) == float(r_on.inertia)
    assert int(r_off.n_iters) == int(r_on.n_iters)
    # the execution contract is untouched: same number of host syncs
    assert s_on.host_syncs == s_off.host_syncs
    assert s_off.ring is None and s_on.ring is not None


# -------------------------------------------------------------------------
# truthful: the ring reconciles exactly with the engine's counters
# -------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_ring_evals_sum_matches_evalcount_exactly(backend):
    pts, init = _dataset(n=2000, d=10, k=16)
    res, stats = engine.fit(pts, init, n_groups=4, max_iters=30,
                            tol=1e-6, backend=backend, interpret=True,
                            tune="off", return_stats=True,
                            obs=ObsConfig(registry=MetricsRegistry()))
    ring = stats.ring
    assert ring.shape == (int(res.n_iters) + 1, N_COUNTERS)
    total = stats.init_evals + float(np.sum(ring[:, COL_EVALS]))
    assert total == float(res.distance_evals)          # EXACT, no rtol
    # the epilogue row carries the converged inertia
    np.testing.assert_allclose(ring[-1, COL_INERTIA],
                               float(res.inertia), rtol=1e-5)


def test_ladder_obs_parity_and_caps_column():
    """The in-trace capacity ladder (down_n/down_g levels switched by
    ``lax.switch``) must stay bit-identical under obs, and the ring's
    cap columns must replay the caps_history the driver reports."""
    pts, init = _dataset(n=3000, d=8, k=24, seed=2)
    cfg = engine.EngineConfig(backend="compact", down_n=2, down_g=2,
                              min_cap=128)
    kw = dict(n_groups=4, max_iters=40, tol=1e-5, config=cfg,
              tune="off", return_stats=True)
    r_off, _ = engine.fit(pts, init, **kw)
    r_on, s_on = engine.fit(pts, init, obs=ObsConfig(
        registry=MetricsRegistry()), **kw)
    np.testing.assert_array_equal(np.asarray(r_off.assignments),
                                  np.asarray(r_on.assignments))
    assert float(r_off.inertia) == float(r_on.inertia)
    assert caps_from_ring(s_on.ring) == s_on.caps_history


def test_engine_stats_to_dict_json_serializable():
    pts, init = _dataset()
    _, stats = engine.fit(pts, init, n_groups=3, max_iters=20,
                          tol=1e-5, backend="compact", tune="off",
                          return_stats=True,
                          obs=ObsConfig(registry=MetricsRegistry()))
    d = stats.to_dict()
    json.dumps(d)                       # must not raise
    assert d["ring_columns"] == list(RING_COLUMNS)
    assert d["telemetry"]["iters"] == int(stats.n_iters)
    assert 0.0 < d["telemetry"]["mean_candidate_fraction"] <= 1.0


def test_kmeans_api_obs_and_stats():
    pts, _ = _dataset()
    reg = MetricsRegistry()
    km = KMeans(12, engine="compact", max_iters=25, tune="off", obs=reg)
    km.fit(pts)
    assert km.stats_ is not None and km.stats_.ring is not None
    assert km.stats_.telemetry()["iters"] == km.n_iter_
    km_plain = KMeans(12, engine="compact", max_iters=25, tune="off")
    km_plain.fit(pts)
    np.testing.assert_array_equal(np.asarray(km.labels_),
                                  np.asarray(km_plain.labels_))
    assert [e for e in reg.events if e["event"] == "engine_fit"]


# -------------------------------------------------------------------------
# live drain
# -------------------------------------------------------------------------

def test_live_drain_emits_every_iteration():
    pts, init = _dataset(n=800, d=6, k=8)
    rows = []
    cb = lambda it, row: rows.append((int(it), row))  # noqa: E731
    add_ring_listener(cb)
    try:
        res, _ = engine.fit(
            pts, init, n_groups=2, max_iters=20, tol=1e-6,
            backend="compact", tune="off", return_stats=True,
            obs=ObsConfig(live_drain=True,
                          registry=MetricsRegistry()))
        jax.effects_barrier()
    finally:
        remove_ring_listener(cb)
    # one row per iteration + the epilogue row
    assert len(rows) == int(res.n_iters) + 1
    assert all(len(r) == N_COUNTERS for _, r in rows)


# -------------------------------------------------------------------------
# shard-ring reductions + the straggler watchdog
# -------------------------------------------------------------------------

def test_reduce_shard_rings_and_skew_arithmetic():
    # synthetic 2-shard ring: shard 1 does 3x the evals of shard 0
    s0 = np.zeros((3, N_COUNTERS), np.float32)
    s1 = np.zeros((3, N_COUNTERS), np.float32)
    s0[:, COL_EVALS] = [10.0, 20.0, 30.0]
    s1[:, COL_EVALS] = [30.0, 60.0, 90.0]
    s0[:, COL_N_CAND] = [5, 4, 3]
    s1[:, COL_N_CAND] = [1, 1, 1]
    s0[:, 1] = [1.0, 2.0, 3.0]          # gmax: reduced by max
    s1[:, 1] = [4.0, 1.0, 1.0]
    rings = np.stack([s0, s1])
    g = reduce_shard_rings(rings)
    np.testing.assert_allclose(g[:, COL_EVALS], [40.0, 80.0, 120.0])
    np.testing.assert_allclose(g[:, COL_N_CAND], [6, 5, 4])
    np.testing.assert_allclose(g[:, 1], [4.0, 2.0, 3.0])
    skew = shard_skew(rings)
    np.testing.assert_allclose(skew, [1.5, 1.5, 1.5])   # max/mean


def test_straggler_watchdog_flags_slow_shard():
    events = []
    wd = StragglerWatchdog(threshold=2.0,
                           on_straggler=events.append)
    # balanced step: nothing flagged, median seeds the EWMA
    assert wd.observe_shards(0, [1.0, 1.1, 0.9, 1.0]) == []
    assert wd.ewma == pytest.approx(1.0)
    # shard 2 does 5x the median work: flagged, EWMA tracks median
    flagged = wd.observe_shards(1, [1.0, 1.0, 5.0, 1.0])
    assert flagged == [2]
    assert events and events[0]["shard"] == 2
    assert events[0]["step"] == 1 and events[0]["median"] == 1.0
    # the outlier didn't poison the EWMA
    assert wd.ewma == pytest.approx(1.0)


def test_distributed_stats_on_single_device_mesh():
    """Tier-1 (1-device) coverage of the distributed stats path: ring
    populated, skew degenerate at 1.0, evals invariant global, stats
    serializable, watchdog fed one observation per iteration."""
    from repro.core.distributed import distributed_yinyang
    pts, init = _dataset(n=1024, d=8, k=12, seed=4)
    mesh = jax.make_mesh((1,), ("data",))
    wd = StragglerWatchdog()
    res, stats = distributed_yinyang(
        pts, init, mesh, n_groups=3, max_iters=25, tol=1e-5,
        backend="compact", return_stats=True,
        obs=MetricsRegistry(), watchdog=wd)
    assert stats.ring is not None
    assert stats.shard_rings.shape[0] == 1
    np.testing.assert_allclose(stats.shard_skew, 1.0)
    total = stats.init_evals + float(np.sum(stats.ring[:, COL_EVALS]))
    assert total == float(res.distance_evals)
    json.dumps(stats.to_dict())
    assert wd.ewma is not None and wd.events == []


# -------------------------------------------------------------------------
# registry / exporters / spans / config coercion
# -------------------------------------------------------------------------

def test_registry_metrics_and_prometheus_text(tmp_path):
    reg = MetricsRegistry()
    reg.counter("fits_total", "fits", labels={"backend": "compact"}).inc(3)
    reg.gauge("last_iters", "iters").set(7.0)
    h = reg.histogram("lat_s", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE fits_total counter" in text
    assert 'fits_total{backend="compact"} 3' in text
    assert "last_iters 7" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 2' in text
    assert "lat_s_count 2" in text
    # get-or-create: same (name, labels) returns the same instrument
    assert reg.counter("fits_total",
                       labels={"backend": "compact"}).value == 3
    p = reg.export_prometheus(tmp_path / "m.prom")
    assert (tmp_path / "m.prom").read_text() == text and p


def test_registry_jsonl_export_and_span(tmp_path):
    reg = MetricsRegistry()
    with span("unit.region", registry=reg, tag="x") as s:
        s["result"] = 42
    reg.log_event("custom", foo="bar")
    path = reg.export_jsonl(tmp_path / "ev.jsonl")
    lines = [json.loads(l) for l in open(path)]
    assert [e["event"] for e in lines] == ["span", "custom"]
    ev = lines[0]
    assert ev["name"] == "unit.region" and ev["tag"] == "x"
    assert ev["result"] == 42 and ev["seconds"] >= 0.0
    # span duration also landed in the labelled histogram
    hist = reg.histogram("span_seconds",
                         labels={"span": "unit.region"})
    assert hist.count == 1


def test_span_without_registry_records_nothing():
    reg = default_registry()
    before = len(reg.events)
    with span("unit.unrecorded", tag="x") as s:
        s["result"] = 1
    assert len(reg.events) == before
    assert "unit.unrecorded" not in reg.to_prometheus()


# -------------------------------------------------------------------------
# host spans on the profiler's clock, compile counter
# -------------------------------------------------------------------------

FIT_PHASES = ("kpynq.seed", "kpynq.tables", "kpynq.loop", "kpynq.epilogue",
              "kpynq.fetch")


def _host_spans(trace_dir):
    """``[(name, start_ns, end_ns)]`` of the ``kpynq.`` host events."""
    import glob
    import os
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns) for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith("kpynq.")]


def test_kmeans_fit_spans_land_in_the_profile(tmp_path):
    pts, _ = _dataset()             # 1500 points: engine.fit's bucketed path
    km = KMeans(12, engine="compact", max_iters=25, tune="off")
    with jax.profiler.trace(str(tmp_path)):
        km.fit(pts)
    spans = _host_spans(tmp_path)
    fits = [(s, e) for n, s, e in spans if n == "kpynq.fit"]
    assert len(fits) == 1
    lo, hi = fits[0]
    names = {n for n, _, _ in spans}
    assert set(FIT_PHASES) <= names
    for name, s, e in spans:
        assert lo <= s <= e <= hi, name
    # one loop span per capacity-bucket segment
    assert sum(n == "kpynq.loop" for n, _, _ in spans) == \
        len(km.stats_.caps_history)


def test_kmeans_fit_bit_identical_under_the_profiler(tmp_path):
    pts, _ = _dataset()
    plain = KMeans(12, engine="compact", max_iters=25, tune="off").fit(pts)
    traced = KMeans(12, engine="compact", max_iters=25, tune="off")
    with jax.profiler.trace(str(tmp_path)):
        traced.fit(pts)
    np.testing.assert_array_equal(np.asarray(plain.cluster_centers_),
                                  np.asarray(traced.cluster_centers_))
    np.testing.assert_array_equal(np.asarray(plain.labels_),
                                  np.asarray(traced.labels_))


def test_move_and_bounds_scopes_the_centroid_sums():
    """The scatter-adds of the centroid sums are traced under
    ``kpynq/centroid_sums``, which ``centroid_sums_ms.fit`` reads; the
    rest of the move (drift, bounds) is not."""
    n, d, k, g = 64, 4, 6, 2
    fn = jax.jit(lambda *a: engine.move_and_bounds(*a, k=k, n_groups=g))
    text = fn.lower(jnp.ones((n, d)), jnp.ones((k, d)),
                    jnp.zeros(n, jnp.int32), jnp.ones(n), jnp.ones((n, g)),
                    jnp.zeros(k, jnp.int32)).as_text(debug_info=True)
    assert "kpynq/centroid_sums/scatter-add" in text
    assert "kpynq/centroid_sums/reduce_max" not in text
    assert "/reduce_max" in text                # the group drift, outside


def test_move_and_bounds_scopes_the_onehot_sums():
    """With ``onehot`` the sums' product and the counts' column sum are
    traced under ``kpynq/centroid_sums`` too, and no scatter-add is left
    there."""
    n, d, k, g = 64, 4, 6, 2
    fn = jax.jit(lambda *a: engine.move_and_bounds(*a, k=k, n_groups=g,
                                                   onehot=True))
    text = fn.lower(jnp.ones((n, d)), jnp.ones((k, d)),
                    jnp.zeros(n, jnp.int32), jnp.ones(n), jnp.ones((n, g)),
                    jnp.zeros(k, jnp.int32)).as_text(debug_info=True)
    assert "kpynq/centroid_sums/nk,nd->kd/dot_general" in text
    assert "kpynq/centroid_sums/reduce_sum" in text
    assert "kpynq/centroid_sums/scatter-add" not in text


def test_onehot_sums_reported_in_stats_and_event(monkeypatch):
    """``EngineStats.onehot_sums`` and the ``engine_fit`` event say which
    sums a fit ran: the scatter on the CPU, the product where the rule
    takes it."""
    pts, _ = _dataset()
    for forced in (False, True):
        if forced:
            monkeypatch.setattr(engine, "onehot_sums", lambda *a, **kw: True)
        reg = MetricsRegistry()
        km = KMeans(12, engine="compact", max_iters=25, tune="off",
                    obs=reg).fit(pts)
        evt, = [e for e in reg.events if e["event"] == "engine_fit"]
        assert km.stats_.onehot_sums is forced
        assert evt["onehot_sums"] is forced


def test_compile_count_counts_a_fresh_jit_once():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(7, dtype=jnp.float32).block_until_ready()
    c0 = compile_count()
    f(x).block_until_ready()
    c1 = compile_count()
    f(x).block_until_ready()
    assert c1 - c0 == 1
    assert compile_count() == c1


def test_kmeans_stats_count_compiles_and_publish_them():
    pts, _ = _dataset()
    reg = MetricsRegistry()
    km = KMeans(12, engine="compact", max_iters=25, tune="off", obs=reg)
    km.fit(pts)
    n = km.stats_.compiles
    assert isinstance(n, int) and n >= 0
    assert km.stats_.to_dict()["compiles"] == n
    total = reg.counter("engine_compiles_total",
                        labels={"backend": "compact"}).value
    assert total == n
    evt, = [e for e in reg.events if e["event"] == "engine_fit"]
    assert evt["compiles"] == n


def test_normalize_obs_coercions():
    assert normalize_obs(None) is None
    assert normalize_obs(False) is None
    cfg = normalize_obs(True)
    assert isinstance(cfg, ObsConfig) and cfg.ring
    reg = MetricsRegistry()
    cfg2 = normalize_obs(reg)
    assert cfg2.resolve_registry() is reg
    assert normalize_obs(cfg2) is cfg2


# -------------------------------------------------------------------------
# streaming driver publishes
# -------------------------------------------------------------------------

def test_streaming_obs_metrics_and_parity():
    from repro.streaming import StreamingKMeans
    pts_np, _, _ = make_points(2400, 8, 10, seed=5)
    reg = MetricsRegistry()
    sk_on = StreamingKMeans(10, n_groups=2, seed=0, tune="off", obs=reg)
    sk_off = StreamingKMeans(10, n_groups=2, seed=0, tune="off")
    for epoch in range(2):
        for i in range(4):
            batch = pts_np[i * 600:(i + 1) * 600]
            sk_on.partial_fit(batch, shard_id=i)
            sk_off.partial_fit(batch, shard_id=i)
    np.testing.assert_array_equal(np.asarray(sk_on.cluster_centers_),
                                  np.asarray(sk_off.cluster_centers_))
    evts = [e for e in reg.events if e["event"] == "stream_batch"]
    assert len(evts) == sk_on.stats_.batches
    assert reg.counter("stream_points_total").value == \
        sk_on.stats_.points_seen
    # epoch 2 re-presents the shards: the bound cache must report hits
    assert any(e["cache_hit"] for e in evts)
    assert sk_on.stats_.to_dict()["cache_hits"] > 0
