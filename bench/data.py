"""Seeded inputs of the benchmark.

``blobs`` repeats the arithmetic of ``repro.data.make_points`` (Gaussian
blobs around centres drawn with ``spread``), drawn with ``jax.random``
in one jitted call on the device, so that a million-point set costs no
host time; the copy keeps the yardstick fixed when the program's
generator changes. ``signed_permutation`` gives a point set new
coordinates with the same geometry: its axes reordered and some of them
reversed, which changes no distance and rounds nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n", "d", "k"))
def _blobs(key, spread, cluster_std, *, n: int, d: int, k: int):
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (k, d), jnp.float32) * spread
    assign = jax.random.randint(ka, (n,), 0, k)
    pts = centers[assign] + jax.random.normal(kn, (n, d), jnp.float32) \
        * cluster_std
    return pts, centers


def blobs(n: int, d: int, k: int, seed: int, *, cluster_std: float = 1.0,
          spread: float = 8.0):
    """``(points (n, d) f32, centres (k, d) f32)``, both on the device."""
    return _blobs(jax.random.PRNGKey(seed), jnp.float32(spread),
                  jnp.float32(cluster_std), n=n, d=d, k=k)


@jax.jit
def _signed_permutation(key, x):
    kp, ks = jax.random.split(key)
    perm = jax.random.permutation(kp, x.shape[1])
    signs = jnp.where(jax.random.bernoulli(ks, 0.5, (x.shape[1],)), -1.0, 1.0)
    return x[:, perm] * signs.astype(x.dtype)


def signed_permutation(x, seed: int):
    """``x`` with its axes permuted and a random half of them negated,
    both drawn from ``seed``: every distance, and so every k-means
    trajectory in exact arithmetic, stays as it was."""
    return _signed_permutation(jax.random.PRNGKey(seed), x)


def seeds(seed: int, count: int) -> list[int]:
    """``count`` independent 31-bit seeds from a run's ``--seed``, which
    may be any whole number."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    return [int(s) & 0x7FFFFFFF for s in ss.generate_state(count)]
