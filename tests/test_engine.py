"""Device-resident engine: parity with Lloyd across every backend.

The engine's contract is the paper's: filters (and their compacted /
block-skipped realisations) change the WORK, never the RESULT. Each
backend must land on Lloyd's fixed point — same assignments, same
inertia — across ragged shapes, single-group (Hamerly) runs, and
iterations where every candidate is filtered out.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KMeans, NotFittedError, kmeans_plusplus, lloyd
from repro.core import engine
from repro.data import make_points

BACKENDS = ["oracle", "compact", "pallas"]


def _dataset(n, d, k, seed=0):
    pts, _, _ = make_points(n, d, k, seed=seed)
    pts = jnp.asarray(pts)
    init = kmeans_plusplus(jax.random.PRNGKey(seed + 1), pts, k)
    return pts, init


def _assert_parity(r_e, r_l):
    assert int(r_e.n_iters) == int(r_l.n_iters)
    np.testing.assert_array_equal(np.asarray(r_e.assignments),
                                  np.asarray(r_l.assignments))
    np.testing.assert_allclose(float(r_e.inertia), float(r_l.inertia),
                               rtol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n,d,k,g", [
    (1000, 8, 12, 3),     # N % tile_n != 0, K < tile_k
    (513, 5, 7, 2),       # ragged everything
    (768, 4, 8, 1),       # single group = Hamerly point-level filter
    (2048, 12, 16, 16),   # one group per centroid
])
def test_engine_matches_lloyd(backend, n, d, k, g):
    pts, init = _dataset(n, d, k)
    r_l = lloyd(pts, init, max_iters=50, tol=1e-5)
    r_e = engine.fit(pts, init, n_groups=g, max_iters=50, tol=1e-5,
                     backend=backend, interpret=True, min_cap=64)
    _assert_parity(r_e, r_l)


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_zero_candidate_iterations(backend):
    # tight, far-apart blobs: after the first assignment the filters
    # eliminate every candidate while centroids still drift (shift>tol)
    pts, _ = _dataset(600, 6, 4, seed=3)
    pts = jnp.asarray(np.asarray(pts) * 0.01)
    centers = jnp.asarray(
        [[0.0] * 6, [100.0] * 6, [-100.0] * 6, [200.0] * 6], jnp.float32)
    pts = pts + centers[jnp.arange(600) % 4]
    init = centers + 0.5
    r_l = lloyd(pts, init, max_iters=20, tol=1e-6)
    r_e, stats = engine.fit(pts, init, n_groups=2, max_iters=20, tol=1e-6,
                            backend=backend, interpret=True, min_cap=64,
                            return_stats=True)
    assert stats.n_iters > 1          # really iterated past the 0-cand step
    _assert_parity(r_e, r_l)


def test_engine_large_path_matches_lloyd():
    # large enough to take the bucketed driver (not the fused small-N
    # path) and to shift capacities at least once
    pts, init = _dataset(6000, 16, 32)
    r_l = lloyd(pts, init, max_iters=50, tol=1e-5)
    r_e, stats = engine.fit(pts, init, n_groups=3, max_iters=50, tol=1e-5,
                            backend="compact", min_cap=256,
                            return_stats=True)
    _assert_parity(r_e, r_l)
    assert len(stats.caps_history) >= 2


def test_engine_no_per_iteration_host_sync():
    """The device-resident claim: host syncs scale with bucket
    transitions (O(log N)), not with iterations."""
    pts, init = _dataset(6000, 16, 32, seed=5)
    r_e, stats = engine.fit(pts, init, n_groups=3, max_iters=50, tol=0.0,
                            backend="compact", return_stats=True)
    assert stats.n_iters > 5
    assert stats.host_syncs < stats.n_iters
    assert stats.host_syncs == len(stats.caps_history) + 1


def test_engine_group_bucket_spill_is_exact():
    """Force a cap_g the data exceeds: the in-pass lax.cond must spill
    to the dense branch, never drop a surviving group."""
    pts, init = _dataset(6000, 8, 24)
    r_l = lloyd(pts, init, max_iters=40, tol=1e-5)
    r_e = engine.fit(pts, init, n_groups=8, max_iters=40, tol=1e-5,
                     backend="compact", max_bucket_switches=1)
    _assert_parity(r_e, r_l)


def test_engine_work_reduction():
    pts, init = _dataset(6000, 16, 32)
    r_l = lloyd(pts, init, max_iters=50, tol=1e-5)
    r_e = engine.fit(pts, init, max_iters=50, tol=1e-5, backend="compact")
    assert float(r_e.distance_evals) < 0.6 * float(r_l.distance_evals)


def test_engine_through_kmeans_api():
    pts, _ = _dataset(1500, 8, 8)
    km_e = KMeans(n_clusters=8, engine="compact", seed=1).fit(pts)
    km_r = KMeans(n_clusters=8, engine=None, seed=1).fit(pts)
    np.testing.assert_array_equal(km_e.labels_, km_r.labels_)
    np.testing.assert_allclose(km_e.inertia_, km_r.inertia_, rtol=1e-5)
    km_h = KMeans(n_clusters=8, algorithm="hamerly", engine="compact",
                  seed=1).fit(pts)
    np.testing.assert_array_equal(km_h.labels_, km_r.labels_)


def test_engine_auto_backend_resolves():
    pts, init = _dataset(512, 4, 4)
    r = engine.fit(pts, init, backend="auto", max_iters=10)
    assert np.isfinite(float(r.inertia))
    with pytest.raises(ValueError):
        engine.fit(pts, init, backend="nope")


def test_engine_auto_routes_tiny_to_lloyd():
    """'auto' routes below the n*k threshold to the dense Lloyd loop
    (see ``engine.AUTO_LLOYD_MAX_WORK``) — and lands on the identical
    fixed point."""
    pts, init = _dataset(512, 8, 16)
    assert 512 * 16 <= engine.AUTO_LLOYD_MAX_WORK
    r, stats = engine.fit(pts, init, backend="auto", max_iters=30,
                          tol=1e-5, return_stats=True)
    assert stats.backend == "lloyd"
    _assert_parity(r, lloyd(pts, init, max_iters=30, tol=1e-5))

    big_pts, big_init = _dataset(4500, 8, 32)
    assert 4500 * 32 > engine.AUTO_LLOYD_MAX_WORK
    _, big_stats = engine.fit(big_pts, big_init, backend="auto",
                              max_iters=10, return_stats=True)
    assert big_stats.backend in ("compact", "pallas")


def test_not_fitted_error():
    km = KMeans(n_clusters=4)
    for attr in ("cluster_centers_", "labels_", "inertia_", "n_iter_",
                 "distance_evals_"):
        with pytest.raises(NotFittedError):
            getattr(km, attr)
    with pytest.raises(NotFittedError):
        km.predict(jnp.zeros((3, 2)))
    # sklearn convention: still catchable as AttributeError/ValueError
    with pytest.raises(AttributeError):
        km.labels_
    with pytest.raises(ValueError):
        km.predict(jnp.zeros((3, 2)))


# -- tiled assignment (the predict path) -----------------------------------

def test_assign_tiled_matches_dense_argmin():
    """engine.assign: the tiled PassCore pass lands on the dense
    argmin for every (N % tile) raggedness, and returns exact
    distances to the assigned centroid."""
    pts, init = _dataset(3000, 8, 24, seed=2)
    r = engine.fit(pts, init, max_iters=20, backend="compact",
                   tune="off")
    d_ref = np.linalg.norm(np.asarray(pts)[:, None]
                           - np.asarray(r.centroids)[None], axis=-1)
    ref = d_ref.argmin(1)
    for tile in (512, 1024, 4096):        # 3000 is ragged vs all three
        labels, dists = engine.assign(pts, r.centroids, tile_n=tile)
        np.testing.assert_array_equal(np.asarray(labels), ref)
        np.testing.assert_allclose(
            np.asarray(dists), d_ref[np.arange(3000), ref], atol=1e-3)


def test_assign_accepts_prebuilt_tables():
    pts, init = _dataset(700, 5, 10, seed=8)
    groups = engine.group_centroids(init, 3)
    members, gsize = engine.build_group_tables(
        np.asarray(jax.device_get(groups)), 3)
    labels, _ = engine.assign(pts, init, groups=groups, members=members,
                              gsize=gsize, tile_n=256)
    ref = np.linalg.norm(np.asarray(pts)[:, None]
                         - np.asarray(init)[None], axis=-1).argmin(1)
    np.testing.assert_array_equal(np.asarray(labels), ref)


# -- the in-trace bucket machinery (consumed by core.distributed) ----------

def test_cap_ladders_shape_and_budget():
    cap_ns, cap_gs = engine.cap_ladders(819, 6, min_cap=256)
    assert cap_ns[0] == 256 and cap_ns[-1] == 819
    assert cap_gs[0] == 1 and cap_gs[-1] == 6
    assert list(cap_ns) == sorted(cap_ns)
    # the branch budget coarsens interiors but never the top endpoints
    cap_ns, cap_gs = engine.cap_ladders(1 << 16, 64, min_cap=64,
                                        max_branches=8)
    assert len(cap_ns) * len(cap_gs) <= 8
    assert cap_ns[-1] == 1 << 16 and cap_gs[-1] == 64
    # degenerate problems collapse to a single level
    assert engine.cap_ladders(100, 1, min_cap=256) == ((100,), (1,))


def test_select_bucket_hysteresis_and_mandatory_upshift():
    cap_ns, cap_gs = (256, 512, 1024), (1, 4, 8)
    kw = dict(cap_ns=cap_ns, cap_gs=cap_gs, down_n=2, down_g=4)

    def sel(n_cand, gmax, ln, lg):
        ln, lg = engine.select_bucket(
            jnp.int32(n_cand), jnp.int32(gmax), jnp.int32(ln),
            jnp.int32(lg), **kw)
        return int(ln), int(lg)

    assert sel(1000, 6, 0, 0) == (2, 2)       # mandatory upshift
    assert sel(300, 2, 1, 1) == (1, 1)        # inside hysteresis: hold
    assert sel(100, 1, 2, 2) == (0, 0)        # past hysteresis: drop
    assert sel(600, 3, 2, 1) == (2, 1)        # 600*2 > 1024: hold
    # gmax == 0 is "no candidates seen", never downshift evidence
    assert sel(100, 0, 2, 2) == (0, 2)
    # down_n=0 / down_g=0 disable that axis entirely
    ln, lg = engine.select_bucket(
        jnp.int32(100), jnp.int32(1), jnp.int32(2), jnp.int32(2),
        cap_ns=cap_ns, cap_gs=cap_gs, down_n=0, down_g=0)
    assert (int(ln), int(lg)) == (2, 2)


def test_ladder_candidate_pass_matches_fixed_cap():
    """The lax.switch'ed pass at any level equals compact_candidate_pass
    at that level's static caps (same numerics, only dispatch added)."""
    pts, init = _dataset(1024, 8, 24, seed=5)
    k, g = 24, 4
    from repro.core.kmeans import _init_filter_state, group_centroids
    from repro.core.distances import row_norms_sq
    groups = engine.group_centroids(init, g)
    groups_np = np.asarray(jax.device_get(groups))
    members, gsize = engine.build_group_tables(groups_np, g)
    x2 = row_norms_sq(pts)
    c2 = row_norms_sq(init)
    st = _init_filter_state(pts, init, groups, g, x2=x2, c2=c2)
    # 200 survivors: inside even the smallest level's capacity (the
    # cap_n >= count precondition holds at every level under test)
    need = jnp.arange(1024) < 200
    cap_ns, cap_gs = (256, 1024), (2, 4)
    for ln in range(2):
        for lg in range(2):
            ref = engine.compact_candidate_pass(
                pts, init, st.assignments, st.ub, st.lb, groups, members,
                gsize, need, cap_n=cap_ns[ln], cap_g=cap_gs[lg],
                n_groups=g, x2=x2, c2=c2)
            out = engine.ladder_candidate_pass(
                pts, init, st.assignments, st.ub, st.lb, groups, members,
                gsize, need, jnp.int32(ln), jnp.int32(lg),
                cap_ns=cap_ns, cap_gs=cap_gs, n_groups=g, x2=x2, c2=c2)
            for a, b in zip(ref, out):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))


def _plain_reference():
    """``bench/reference.py``: plain Lloyd at ``HIGHEST`` and its squared
    distances, written out without ``repro.core``."""
    path = pathlib.Path(__file__).resolve().parents[1] / "bench" / "reference.py"
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("eng,backend", [("pallas", "pallas"),
                                         ("auto", "compact")])
def test_census_shape_matches_plain_lloyd(eng, backend):
    """census1990-d68-k50's shape class at a CPU size: N = 9,645 (ragged
    against the 256-point tile), D = 68, K = 50 in G = 5 groups,
    overlapping blobs (spread 1) and 20 fixed iterations, fitted through
    ``KMeans`` on the interpreted Pallas kernel and on the ``auto``
    path (the compact backend on the CPU), against plain Lloyd from the
    same k-means++ seeds."""
    n, d, k, g, iters = 9_645, 68, 50, 5, 20
    pts = jnp.asarray(make_points(n, d, k, seed=0, spread=1.0)[0])
    init = kmeans_plusplus(jax.random.PRNGKey(0), pts, k)
    ref = _plain_reference()
    c_ref, a_ref, _ = ref.lloyd(pts, init, max_iters=iters, tol=-1.0)
    d2 = ref.sq_dists(pts, c_ref)
    km = KMeans(n_clusters=k, n_groups=g, engine=eng, max_iters=iters,
                tol=-1.0, seed=0).fit(pts)
    assert km.stats_.backend == backend and km.n_iter_ == iters
    # A label may differ from the reference's only at a near-tie: the two
    # centroids' squared distances equal within float32 rounding of the
    # ~10^2-sized terms summed in another order (relative 1e-5).
    labels, a_ref, d2 = (np.asarray(v) for v in (km.labels_, a_ref, d2))
    off = np.nonzero(labels != a_ref)[0]
    own, best = d2[off, labels[off]], d2[off, a_ref[off]]
    assert np.all(np.abs(own - best) <= 1e-5 * best)
    # Equal labels give equal means up to the order of a float32 sum over
    # ~190 points of norm ~10: 1e-5 absolute. No near-tie flipped here, so
    # a flip (which moves two centroids by ~0.04) fails this too.
    np.testing.assert_allclose(np.asarray(km.cluster_centers_),
                               np.asarray(c_ref), rtol=0, atol=1e-5)


@pytest.mark.parametrize("k,d,platform,weighted,want", [
    (50, 68, "tpu", False, True),          # census1990-d68-k50
    (1_024, 128, "tpu", False, True),      # sift128-ivf1024
    (2_048, 128, "tpu", False, True),      # the largest K*D timed a win
    (4_096, 128, "tpu", False, False),     # timed a loss
    (16_384, 128, "tpu", False, False),    # N*K*D of MXU work loses
    (50, 68, "tpu", True, False),          # weighted sums keep the scatter
    (50, 68, "cpu", False, False),
    (1_024, 128, "cpu", False, False),
])
def test_onehot_sums_rule(k, d, platform, weighted, want):
    assert engine.onehot_sums(k, d, platform=platform,
                              weighted=weighted) is want


def test_census_shape_onehot_sums_match_plain_lloyd(monkeypatch):
    """The batch fit with the one-hot product forced on (the rule takes
    it on the TPU only) at census1990-d68-k50's CPU shape, against plain
    Lloyd from the same k-means++ seeds; the stats say which path ran."""
    n, d, k, g, iters = 9_645, 68, 50, 5, 20
    pts = jnp.asarray(make_points(n, d, k, seed=0, spread=1.0)[0])
    init = kmeans_plusplus(jax.random.PRNGKey(0), pts, k)
    ref = _plain_reference()
    c_ref, a_ref, _ = ref.lloyd(pts, init, max_iters=iters, tol=-1.0)
    d2 = np.asarray(ref.sq_dists(pts, c_ref))
    km = KMeans(n_clusters=k, n_groups=g, engine="auto", max_iters=iters,
                tol=-1.0, seed=0).fit(pts)
    assert not km.stats_.onehot_sums
    monkeypatch.setattr(engine, "onehot_sums", lambda *a, **kw: True)
    km = KMeans(n_clusters=k, n_groups=g, engine="auto", max_iters=iters,
                tol=-1.0, seed=0).fit(pts)
    assert km.stats_.onehot_sums and km.stats_.to_dict()["onehot_sums"]
    assert km.n_iter_ == iters
    # the same near-tie and centroid tolerances as the scatter's fit above
    labels, a_ref = np.asarray(km.labels_), np.asarray(a_ref)
    off = np.nonzero(labels != a_ref)[0]
    own, best = d2[off, labels[off]], d2[off, a_ref[off]]
    assert np.all(np.abs(own - best) <= 1e-5 * best)
    np.testing.assert_allclose(np.asarray(km.cluster_centers_),
                               np.asarray(c_ref), rtol=0, atol=1e-5)


def _scatter_cap(lb, old_group, changed, ub_t):
    """The lower-bound cap as the reference step writes it."""
    rows = jnp.arange(lb.shape[0])
    return lb.at[rows, old_group].min(jnp.where(changed, ub_t, jnp.inf))


@pytest.mark.parametrize("n,g", [(4_096 + 173, 5), (4_096 + 173, 102),
                                 (8_192, 102)])
def test_cap_old_group_matches_scatter_min_bits(n, g):
    """The one-hot select gives the scatter-min's bounds bit for bit:
    ragged N, changed and unchanged rows, infinite bounds, and caps that
    bind (bound above ``ub_t``) or not (bound below)."""
    rng = np.random.default_rng(n + g)
    lb = rng.uniform(0.0, 4.0, (n, g)).astype(np.float32)
    lb[rng.random((n, g)) < 0.1] = np.inf
    ub_t = rng.uniform(0.0, 4.0, n).astype(np.float32)
    ub_t[:7] = lb[np.arange(7), 0]          # ties with the bound
    changed = rng.random(n) < 0.3
    old_group = rng.integers(0, g, n).astype(np.int32)
    old_group[:7] = 0
    lb, ub_t, changed, old_group = map(jnp.asarray,
                                       (lb, ub_t, changed, old_group))
    want = _scatter_cap(lb, old_group, changed, ub_t)
    got = jax.jit(engine.cap_old_group)(lb, old_group, changed, ub_t)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    # the case is not vacuous: some caps bind, on finite and inf bounds
    bound = np.asarray(got) != np.asarray(lb)
    assert bound.sum() > n // 20
    assert np.isinf(np.asarray(lb)[bound]).any()


def test_pallas_pass_lower_bounds_match_reference_step(monkeypatch):
    """One iteration mid-fit, at a ragged N: the interpreted Pallas
    candidate pass, fed the engine's move of the same state, returns the
    labels of ``kmeans._filtered_step`` (whose cap is the scatter-min),
    and its (N, G) lower bounds bit for bit wherever the kernel computed
    just the groups the point needed. (A live block computes a group for
    every point of its tile, which may tighten other rows' bounds.) The
    rows are sorted by the groups they need, so that whole tiles are of
    one kind, and among them are rows that left a group the pass
    skipped."""
    from repro.core.distances import row_norms_sq
    from repro.core.kmeans import _filtered_step, _init_filter_state
    from repro.kernels import build_group_block_mask
    n, d, k, g, tile_n = 4_096 + 173, 8, 50, 5, 256
    pts = jnp.asarray(make_points(n, d, k, seed=3, spread=1.0)[0])
    init = kmeans_plusplus(jax.random.PRNGKey(3), pts, k)
    groups = engine.group_centroids(init, g)
    members, gsize = engine.build_group_tables(
        np.asarray(jax.device_get(groups)), g)

    def move(pts, st):
        return engine.move_and_bounds(
            pts, st.centroids, st.assignments, st.ub, st.lb, groups, k=k,
            n_groups=g, x2=row_norms_sq(pts))

    def group_need(mo):
        return np.asarray(mo.need[:, None] & (mo.lb < mo.ub[:, None]))

    st = _init_filter_state(pts, init, groups, g)
    for _ in range(2):
        st = _filtered_step(pts, st, groups, g, k)
    perm = np.argsort(group_need(move(pts, st)) @ (1 << np.arange(g)),
                      kind="stable")
    pts = pts[perm]
    st = st._replace(assignments=st.assignments[perm], ub=st.ub[perm],
                     lb=st.lb[perm])
    x2 = row_norms_sq(pts)
    ref = _filtered_step(pts, st, groups, g, k, x2=x2)
    mo = move(pts, st)
    np.testing.assert_array_equal(np.asarray(mo.centroids),
                                  np.asarray(ref.centroids))

    def pallas_pass():
        return engine.pallas_candidate_pass(
            pts, mo.centroids, st.assignments, mo.ub, mo.lb, groups,
            members, gsize, mo.need, n_groups=g, tile_n=tile_n,
            interpret=True, x2=x2, c2=mo.c2)

    new_assign, _, new_lb, _ = pallas_pass()
    np.testing.assert_array_equal(np.asarray(new_assign),
                                  np.asarray(ref.assignments))
    gn = group_need(mo)
    mask = np.asarray(build_group_block_mask(jnp.asarray(gn),
                                             tile_n=tile_n))
    exact = (mask[np.arange(n) // tile_n] == gn).all(axis=1)
    np.testing.assert_array_equal(np.asarray(new_lb)[exact].view(np.uint32),
                                  np.asarray(ref.lb)[exact].view(np.uint32))
    rows = np.arange(n)
    old = np.asarray(groups)[np.asarray(st.assignments)]
    binds = (exact & (np.asarray(new_assign) != np.asarray(st.assignments))
             & ~gn[rows, old]
             & (np.asarray(mo.lb)[rows, old] > np.asarray(mo.ub)))
    assert binds.sum() > 0
    # every row, the pass with the scatter-min in the select's place
    monkeypatch.setattr(engine, "cap_old_group", _scatter_cap)
    np.testing.assert_array_equal(
        np.asarray(new_lb).view(np.uint32),
        np.asarray(pallas_pass()[2]).view(np.uint32))
