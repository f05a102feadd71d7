"""Core K-means behaviour: exactness of the multi-level filters.

The central claim of the paper's algorithm layer: the triangle-
inequality filters NEVER change the result — only the work. So filtered
K-means must match Lloyd bit-for-bit (same assignments, same centroids)
while doing strictly fewer distance evaluations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (KMeans, engine, group_centroids, kmeans_plusplus,
                        lloyd, random_init, yinyang)
from repro.core.kmeans import onehot_centroid_sums
from repro.data import make_points


def _dataset(n=3000, d=12, k=16, seed=0):
    pts, _, _ = make_points(n, d, k, seed=seed)
    init = kmeans_plusplus(jax.random.PRNGKey(seed + 1), jnp.asarray(pts), k)
    return jnp.asarray(pts), init, k


@pytest.mark.parametrize("n_groups", [1, 4, None])
def test_filtered_matches_lloyd_exactly(n_groups):
    pts, init, k = _dataset()
    r_l = lloyd(pts, init, max_iters=50, tol=1e-5)
    r_f = yinyang(pts, init, n_groups=n_groups, max_iters=50, tol=1e-5)
    assert int(r_l.n_iters) == int(r_f.n_iters)
    np.testing.assert_array_equal(np.asarray(r_l.assignments),
                                  np.asarray(r_f.assignments))
    np.testing.assert_allclose(np.asarray(r_l.centroids),
                               np.asarray(r_f.centroids), atol=1e-4)


def test_filters_reduce_work():
    pts, init, k = _dataset(n=6000, k=32)
    r_l = lloyd(pts, init, max_iters=50, tol=1e-5)
    r_h = yinyang(pts, init, n_groups=1, max_iters=50, tol=1e-5)
    r_y = yinyang(pts, init, max_iters=50, tol=1e-5)
    assert float(r_h.distance_evals) < float(r_l.distance_evals)
    assert float(r_y.distance_evals) < float(r_h.distance_evals)
    # clustered data after warmup should prune the large majority
    assert float(r_y.distance_evals) < 0.5 * float(r_l.distance_evals)


def test_inertia_monotone_nonincreasing_across_iters():
    pts, init, k = _dataset(n=2000, k=8, seed=3)
    prev = None
    for iters in (1, 2, 4, 8):
        r = lloyd(pts, init, max_iters=iters, tol=0.0)
        val = float(r.inertia)
        if prev is not None:
            assert val <= prev + 1e-3
        prev = val


def test_kmeans_plusplus_beats_random_init():
    pts, _, k = _dataset(n=4000, d=8, k=24, seed=5)
    key = jax.random.PRNGKey(7)
    init_pp = kmeans_plusplus(key, pts, k)
    init_rand = random_init(key, pts, k)
    r_pp = lloyd(pts, init_pp, max_iters=1, tol=0.0)
    r_rand = lloyd(pts, init_rand, max_iters=1, tol=0.0)
    assert float(r_pp.inertia) < float(r_rand.inertia)


def test_group_centroids_partition():
    c = jax.random.normal(jax.random.PRNGKey(0), (40, 6))
    g = group_centroids(c, 5)
    assert g.shape == (40,)
    assert int(g.min()) >= 0 and int(g.max()) < 5


def test_sklearn_style_api():
    pts, _, _ = _dataset(n=1500, k=8)
    km = KMeans(n_clusters=8, algorithm="yinyang", seed=1).fit(pts)
    km_l = KMeans(n_clusters=8, algorithm="lloyd", seed=1).fit(pts)
    assert km.labels_.shape == (1500,)
    assert km.cluster_centers_.shape == (8, pts.shape[1])
    np.testing.assert_allclose(km.inertia_, km_l.inertia_, rtol=1e-5)
    assert km.distance_evals_ < km_l.distance_evals_
    pred = km.predict(pts[:10])
    np.testing.assert_array_equal(pred, km.labels_[:10])


def test_sklearn_parity_predict_transform_score():
    """The sklearn-parity inference surface on top of the tiled
    assign: transform is the (N, K) distance space, predict its
    argmin, score the negative inertia, fit_predict the training
    labels."""
    pts, _, k = _dataset(n=2000, k=8, seed=2)
    km = KMeans(n_clusters=8, seed=1, engine="compact",
                tune="off").fit(pts)
    T = km.transform(pts[:300])
    assert T.shape == (300, 8)
    d_ref = np.linalg.norm(np.asarray(pts[:300])[:, None]
                           - np.asarray(km.cluster_centers_)[None],
                           axis=-1)
    np.testing.assert_allclose(T, d_ref, atol=1e-3)
    np.testing.assert_array_equal(km.predict(pts[:300]), d_ref.argmin(1))
    # score == -inertia on the training set
    assert km.score(pts) == pytest.approx(-km.inertia_, rel=1e-4)
    km2 = KMeans(n_clusters=8, seed=1, engine="compact", tune="off")
    np.testing.assert_array_equal(km2.fit_predict(pts), km.labels_)


def test_predict_tiled_beyond_one_tile():
    """predict runs tiled (ragged N >> tile) and still matches the
    dense argmin — the no-O(N*K)-buffer contract of the new path."""
    pts, _, _ = _dataset(n=20000, k=12, seed=4)
    km = KMeans(n_clusters=12, seed=1, engine="compact",
                tune="off").fit(pts)
    got = km.predict(pts)
    ref = np.linalg.norm(
        np.asarray(pts)[:, None]
        - np.asarray(km.cluster_centers_)[None], axis=-1).argmin(1)
    np.testing.assert_array_equal(got, ref)


def test_empty_cluster_keeps_previous_centroid():
    # two far blobs, k=3: one centroid starts far away and owns nothing
    pts = jnp.concatenate([
        jnp.ones((50, 2)), -jnp.ones((50, 2))])
    init = jnp.asarray([[1.0, 1.0], [-1.0, -1.0], [100.0, 100.0]])
    r = lloyd(pts, init, max_iters=5, tol=1e-6)
    assert np.isfinite(np.asarray(r.centroids)).all()
    r_y = yinyang(pts, init, n_groups=1, max_iters=5, tol=1e-6)
    np.testing.assert_array_equal(np.asarray(r.assignments),
                                  np.asarray(r_y.assignments))


def test_distance_evals_counter_is_precision_safe():
    """Regression: a bare fp32 accumulator silently drops increments
    once the total passes 2^24 (one paper-scale iteration adds N*K ~
    10^8). The compensated EvalCount pair must keep exact integer
    counts far beyond that."""
    from repro.core import EvalCount

    naive = jnp.float32(2 ** 24)
    c = EvalCount.of(2 ** 24)
    for _ in range(64):
        naive = naive + jnp.float32(1.0)
        c = c.add(1.0)
    assert float(naive) == 2 ** 24          # the bug: +1 x64 vanished
    assert float(c.total()) == 2 ** 24 + 64

    # paper-scale accumulation: 50 iterations of N*K = 2^27 evals
    c = EvalCount.of(0)
    for _ in range(50):
        c = c.add(jnp.float32(2 ** 27))
    assert float(c.total()) == 50 * 2 ** 27

    # odd increments force rounding on almost every add; the (hi, lo)
    # pair must still hold the exact integer (total() rounds once)
    @jax.jit
    def accumulate(c0):
        def body(_, c):
            return c.add(2 ** 24 - 1)
        return jax.lax.fori_loop(0, 100, body, c0)
    c = accumulate(EvalCount.of(0))
    exact = np.float64(np.asarray(c.hi)) + np.float64(np.asarray(c.lo))
    assert exact == 100 * (2 ** 24 - 1)


@pytest.mark.parametrize("driver", ["kmeans_api", "engine_fit"])
def test_compact_path_matches_lloyd(driver):
    """The engine's ``compact`` backend, through ``KMeans`` and through
    ``engine.fit``, lands on Lloyd's fixed point with fewer distance
    evaluations."""
    pts, init, k = _dataset(n=4000, k=24, seed=7)
    r_l = lloyd(pts, init, max_iters=40, tol=1e-5)
    if driver == "kmeans_api":
        # KMeans(seed=8) seeds with PRNGKey(8), as _dataset(seed=7) does
        r_c = KMeans(n_clusters=k, seed=8, max_iters=40, tol=1e-5,
                     engine="compact", tune="off").fit(pts).result_
    else:
        r_c = engine.fit(pts, init, max_iters=40, tol=1e-5,
                         backend="compact", tune="off")
    np.testing.assert_allclose(float(r_l.inertia), float(r_c.inertia),
                               rtol=1e-5)
    agree = (np.asarray(r_l.assignments) ==
             np.asarray(r_c.assignments)).mean()
    assert agree > 0.999  # fp-tie divergence only
    assert float(r_c.distance_evals) < float(r_l.distance_evals)


@pytest.mark.parametrize("n,d,k,labels", [
    (9_645, 68, 50, "spread"),        # census width, N 173 past 256 * 37
    (9_645, 128, 1_024, "spread"),    # sift width at IVF1024
    (9_645, 68, 50, "one"),           # every point in one cluster
    (2_048, 128, 1_024, "one"),
])
def test_onehot_centroid_sums_match_float64(n, d, k, labels):
    """The one-hot product's sums against float64 numpy: within a float32
    accumulation bound (16 ulps of the summed magnitudes), counts exact."""
    rng = np.random.default_rng(n + d + k)
    x = (rng.normal(size=(n, d)) * 3.0 + 5.0).astype(np.float32)
    a = rng.integers(0, k, n) if labels == "spread" else np.full(n, k - 1)
    a = a.astype(np.int32)
    sums, counts = jax.jit(onehot_centroid_sums, static_argnums=2)(
        jnp.asarray(x), jnp.asarray(a), k)
    ref = np.zeros((k, d))
    np.add.at(ref, a, x.astype(np.float64))
    mag = np.zeros((k, d))
    np.add.at(mag, a, np.abs(x.astype(np.float64)))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(a, minlength=k))
    err = np.abs(np.asarray(sums, np.float64) - ref)
    assert np.all(err <= 16 * np.finfo(np.float32).eps * mag)

