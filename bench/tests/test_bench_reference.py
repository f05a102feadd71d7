"""The plain references: blocked assignment on a ragged size, Lloyd's
fixed iteration count, and the precision control's distance from
``HIGHEST``; and the fit mix's new coordinates, which keep every
distance."""
import numpy as np
import pytest

import checks  # noqa: F401  (puts the benchmark's modules on the path)

import jax.numpy as jnp
import data
import reference as ref


def _points(n, d, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (n, d)).astype(np.float32))


@pytest.mark.parametrize("n", [20_001, 8_192, 5])
def test_blocked_assign_matches_one_block(n):
    x = _points(n, 6)
    c = x[:7] if n >= 7 else _points(7, 6, 1)
    whole = jnp.argmin(ref.sq_dists(x, c), axis=1)
    assert np.array_equal(np.asarray(ref._assign(x, c, "highest")),
                          np.asarray(whole))


def test_lloyd_without_a_convergence_test_runs_every_iteration():
    x = _points(4_099, 4)
    _, labels, moves = ref.lloyd(x, x[:5], max_iters=30, tol=-1.0)
    assert int(moves) == 30 and labels.shape == (4_099,)
    _, _, moves = ref.lloyd(x, x[:5], max_iters=500, tol=0.0)
    assert int(moves) < 500                 # it reaches a fixed point


def test_bf16_3x_is_a_step_below_highest():
    x, c = _points(512, 68), _points(50, 68, 1)
    hi = np.asarray(ref.cross(x, c, "highest"), np.float64)
    lo = np.asarray(ref.cross(x, c, "bf16_3x"), np.float64)
    err = np.max(np.abs(lo - hi)) / np.max(np.abs(hi))
    assert 1e-7 < err < 1e-4


def test_signed_permutation_keeps_every_distance():
    x = _points(300, 17)
    y = data.signed_permutation(x, 2**40 + 3)
    assert not np.array_equal(np.asarray(x), np.asarray(y))
    assert np.array_equal(np.sort(np.abs(np.asarray(x)), axis=1),
                          np.sort(np.abs(np.asarray(y)), axis=1))
    gram = lambda a: np.asarray(a, np.float64) @ np.asarray(a, np.float64).T
    assert np.allclose(gram(x), gram(y), rtol=0, atol=1e-9)
    assert np.array_equal(np.asarray(data.signed_permutation(x, 5)),
                          np.asarray(data.signed_permutation(x, 5)))
