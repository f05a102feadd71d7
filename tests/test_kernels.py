"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(Pallas interpret=True executes the kernel body on CPU), plus the core
distance and centroid-sum primitives every driver calls."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distances import pairwise_sq_dists
from repro.core.kmeans import centroid_sums, onehot_centroid_sums
from repro.kernels import (build_group_block_mask, compact_indices,
                           grouped_assign)
from repro.kernels.ref import (centroid_update_ref, grouped_assign_ref,
                               pairwise_sq_dists_ref)

# the module, which the package's function of the same name shadows
grouped_assign_mod = importlib.import_module("repro.kernels.grouped_assign")

SHAPES = [  # (n, d, k) including non-aligned sizes that exercise padding
    (256, 16, 128), (1000, 48, 300), (130, 7, 17), (512, 128, 128),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("n,d,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_sq_dists(n, d, k, dtype):
    kx, kc = jax.random.split(jax.random.PRNGKey(n + k))
    x = jax.random.normal(kx, (n, d), dtype)
    c = jax.random.normal(kc, (k, d), dtype)
    got = pairwise_sq_dists(x, c)
    want = pairwise_sq_dists_ref(x, c)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


def _bucket(c, groups, g, lmax=1):
    """(K, D) centroids + (K,) group ids -> the kernel's (G, Lmax, D)
    group buckets and (G, Lmax) id table (-1 = pad)."""
    groups = np.asarray(groups)
    lmax = max(int(np.bincount(groups, minlength=g).max()), lmax)
    members = np.full((g, lmax), -1, np.int32)
    for gg in range(g):
        ids = np.nonzero(groups == gg)[0]
        members[gg, :len(ids)] = ids
    ids = jnp.asarray(members)
    return c[jnp.maximum(ids, 0)], ids


@pytest.mark.parametrize("n,d,k", SHAPES)
@pytest.mark.parametrize("density", [0.0, 0.35, 1.0])
def test_grouped_assign_block_skip(n, d, k, density):
    """A random (tile, group) block mask: live blocks score as the
    oracle does, and a row with no live group comes back as no
    candidate (+inf, -1)."""
    tile_n, g = 256, max(k // 16, 1)
    kx, kc, kg, km = jax.random.split(jax.random.PRNGKey(n * k + 1), 4)
    x = jax.random.normal(kx, (n, d))
    c = jax.random.normal(kc, (k, d))
    c_grouped, ids = _bucket(c, jax.random.randint(kg, (k,), 0, g), g)
    mask = jax.random.bernoulli(km, density, (-(-n // tile_n), g))
    best, idx, *_ = grouped_assign(x, c_grouped, ids, mask, tile_n=tile_n,
                                   interpret=True)
    bref, iref, *_ = grouped_assign_ref(x, c_grouped, ids, mask, tile_n)
    finite = np.isfinite(np.asarray(bref))
    np.testing.assert_allclose(np.asarray(best)[finite],
                               np.asarray(bref)[finite], rtol=1e-5,
                               atol=1e-5)
    assert (~finite == (np.asarray(idx) == -1)).all()
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))


@pytest.mark.parametrize("n,d,k,g,tile_n,density,lmax,budget", [
    (300, 7, 17, 4, 128, 0.5, None, None),    # ragged N/K, partial skip
    (512, 16, 64, 8, 256, 1.0, None, None),   # aligned, fully dense
    (1000, 12, 40, 5, 256, 0.3, None, None),  # mostly skipped
    (130, 3, 6, 6, 64, 0.0, None, None),      # everything skipped
    # a budget too small for one group: 8 groups a step, G=20 leaves a
    # partial last group block
    (384, 8, 60, 20, 128, 0.5, None, 1),
    (520, 10, 64, 8, 256, 0.6, 32, None),     # Lmax 4x the mean group
    (512, 16, 64, 8, 256, "halves", None, None),  # dead tile, live tile
])
def test_grouped_assign_matches_ref(monkeypatch, n, d, k, g, tile_n,
                                    density, lmax, budget):
    if budget is not None:
        monkeypatch.setattr(grouped_assign_mod, "GROUP_VMEM_BUDGET", budget)
    kx, kc, kg, km = jax.random.split(jax.random.PRNGKey(n + k), 4)
    x = jax.random.normal(kx, (n, d))
    c = jax.random.normal(kc, (k, d))
    c_grouped, ids = _bucket(c, jax.random.randint(kg, (k,), 0, g), g,
                             lmax or 1)
    gs = grouped_assign_mod.groups_per_step(g, ids.shape[1], d, tile_n)
    assert gs < g if budget is not None else gs == g
    gn = -(-n // tile_n)
    if density == "halves":    # even tiles need no group, odd ones all
        mask = jnp.repeat((jnp.arange(gn) % 2 == 1)[:, None], g, axis=1)
    else:
        mask = jax.random.bernoulli(km, density, (gn, g))
    got = grouped_assign(x, c_grouped, ids, mask, tile_n=tile_n,
                         interpret=True)
    want = grouped_assign_ref(x, c_grouped, ids, mask, tile_n)
    for name, a, b in zip(("best", "idx", "gmin", "garg", "gmin2"),
                          got, want):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind == "f":
            finite = np.isfinite(b)
            assert (np.isfinite(a) == finite).all(), name
            np.testing.assert_allclose(a[finite], b[finite], rtol=1e-5,
                                       atol=1e-5, err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_groups_per_step_rule():
    """All groups in one step at the IVF1024 cell's shape (D=128, G=102,
    Lmax 72); at K=16,384 (G=1,638) the largest multiple of 8 whose
    blocks fit the budget."""
    rule = grouped_assign_mod.groups_per_step
    size = grouped_assign_mod._group_block_bytes
    budget = grouped_assign_mod.GROUP_VMEM_BUDGET
    assert rule(102, 72, 128, 256) == 102
    gs = rule(1638, 72, 128, 256)
    assert gs < 1638 and gs % 8 == 0
    assert size(gs, 72, 128, 256) <= budget < size(gs + 8, 72, 128, 256)
    assert budget < grouped_assign_mod.VMEM_LIMIT


def test_group_block_mask_construction():
    need = jnp.zeros((600, 4), bool).at[300:, 1].set(True)
    mask = build_group_block_mask(need, tile_n=256)
    # rows 300.. span tiles 1 and 2 only; they need group 1 only
    expected = np.zeros((3, 4), bool)
    expected[1:, 1] = True
    np.testing.assert_array_equal(np.asarray(mask), expected)


@pytest.mark.parametrize("n,d,k", SHAPES)
@pytest.mark.parametrize("sums_fn", [centroid_sums, onehot_centroid_sums])
def test_centroid_sums_match_ref(n, d, k, sums_fn):
    kx, ka = jax.random.split(jax.random.PRNGKey(n + d))
    x = jax.random.normal(kx, (n, d))
    a = jax.random.randint(ka, (n,), 0, k)
    sums, counts = sums_fn(x, a, k)
    sref, cref = centroid_update_ref(x, a, k)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(cref))


def test_fused_auto_path_equals_bruteforce_when_dense():
    """Every group needed: the filter's mask, through the kernel, lands
    on the brute-force argmin."""
    kx, kc = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (500, 24))
    c = jax.random.normal(kc, (64, 24))
    c_grouped, ids = _bucket(c, np.arange(64) % 4, 4)
    mask = build_group_block_mask(jnp.ones((500, 4), bool), tile_n=256)
    assert bool(mask.all())
    _, idx, *_ = grouped_assign(x, c_grouped, ids, mask, tile_n=256,
                                interpret=True)
    want = pairwise_sq_dists_ref(x, c)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.asarray(jnp.argmin(want, axis=1)))


def test_compact_indices_matches_nonzero():
    m = jax.random.bernoulli(jax.random.PRNGKey(2), 0.2, (777,))
    idx, valid, count = compact_indices(m, capacity=777)
    ref = np.nonzero(np.asarray(m))[0]
    assert int(count) == len(ref)
    np.testing.assert_array_equal(np.asarray(idx)[:len(ref)], ref)
    assert int(valid.sum()) == len(ref)


@pytest.mark.parametrize("b,h,s,d,bq,bk", [
    (2, 3, 128, 32, 64, 32), (1, 2, 256, 64, 256, 64),
    (1, 1, 64, 16, 16, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, h, s, d, bq, bk, dtype):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref
    key = jax.random.PRNGKey(s + d)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), dtype)
               for kk in jax.random.split(key, 3))
    got = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    want = flash_attention_ref(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("g,q,n,p_", [(4, 32, 16, 32), (2, 128, 8, 64),
                                      (1, 16, 128, 16)])
def test_ssd_intra(g, q, n, p_):
    from repro.kernels.ref import ssd_intra_ref
    from repro.kernels.ssd_intra import ssd_intra
    key = jax.random.PRNGKey(g + q)
    kc, kb, kx, kd = jax.random.split(key, 4)
    c = jax.random.normal(kc, (g, q, n))
    b = jax.random.normal(kb, (g, q, n))
    x = jax.random.normal(kx, (g, q, p_))
    # realistic negative log-decay accumulation
    cum = jnp.cumsum(-jax.nn.softplus(
        jax.random.normal(kd, (g, q))), axis=1)
    got = ssd_intra(c, b, x, cum, interpret=True)
    want = ssd_intra_ref(c, b, x, cum)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
