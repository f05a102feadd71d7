"""Distance primitives shared by the K-means family.

All bound arithmetic is fp32 (the filters must never prune the true
nearest centroid), and so is the cross term: every distance product
runs at :data:`CROSS_PRECISION` (``HIGHEST``). The TPU's default f32
matmul is one bf16 pass, whose error in ``x.c`` is comparable to the
squared-distance gap between near-tied centroids: enough to flip
labels and to let a bound prune the true nearest centroid. This module
is the pure-jnp reference semantics used by the algorithm layer and
the oracles.

Every pairwise primitive accepts optional precomputed squared norms
(``x2`` for rows, ``c2`` for centroids).  Point norms never change
during a fit and centroid norms change once per iteration, so the
callers (engine / reference loops) compute ``||x||^2`` ONCE PER FIT and
``||c||^2`` once per iteration and thread them through — recomputing
them inside every distance call was measurable on the hot path
(ISSUE 3). Passing ``None`` recomputes locally (reference semantics,
bit-identical: the same ``sum(x*x)`` expression either way).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# precision of every distance cross term x.c (the engine's compact
# pass, the serve assign and the Pallas kernel use the same)
CROSS_PRECISION = jax.lax.Precision.HIGHEST


def row_norms_sq(x: jnp.ndarray) -> jnp.ndarray:
    """``||x_i||^2`` per row, (N, D) -> (N,) fp32 — THE norm expression
    shared by every distance path (callers cache its output)."""
    x = x.astype(jnp.float32)
    return jnp.sum(x * x, axis=-1)


def pairwise_sq_dists(x: jnp.ndarray, c: jnp.ndarray,
                      x2: jnp.ndarray | None = None,
                      c2: jnp.ndarray | None = None) -> jnp.ndarray:
    """Squared Euclidean distances, (N, D) x (K, D) -> (N, K).

    Expanded as ||x||^2 - 2 x.c + ||c||^2 so the dominant term is a
    single (N, D) x (D, K) matmul (MXU-friendly on the target hardware).
    ``x2`` / ``c2``: optional precomputed squared norms (see module
    docstring).
    """
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    if x2 is None:
        x2 = row_norms_sq(x)
    if c2 is None:
        c2 = row_norms_sq(c)
    d2 = x2[:, None] - 2.0 * jnp.dot(x, c.T, precision=CROSS_PRECISION) \
        + c2[None, :]
    return jnp.maximum(d2, 0.0)                           # numerical floor


def pairwise_dists(x: jnp.ndarray, c: jnp.ndarray,
                   x2: jnp.ndarray | None = None,
                   c2: jnp.ndarray | None = None) -> jnp.ndarray:
    return jnp.sqrt(pairwise_sq_dists(x, c, x2, c2))


def rowwise_dists(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """d(x_i, c_i) for paired rows, (N, D) x (N, D) -> (N,)."""
    diff = x.astype(jnp.float32) - c.astype(jnp.float32)
    return jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0))
