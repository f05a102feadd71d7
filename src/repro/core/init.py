"""Centroid initialization: random subset and k-means++ (both jittable)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .distances import pairwise_sq_dists


def random_init(key: jax.Array, points: jnp.ndarray, k: int) -> jnp.ndarray:
    idx = jax.random.choice(key, points.shape[0], shape=(k,), replace=False)
    return points[idx].astype(jnp.float32)


def kmeans_plusplus(key: jax.Array, points: jnp.ndarray, k: int,
                    weights: jnp.ndarray | None = None) -> jnp.ndarray:
    """k-means++ seeding (Arthur & Vassilvitskii) as a lax.fori_loop.

    ``weights``: optional (N,) nonnegative per-point weights. The first
    centroid is drawn proportional to w, each subsequent one
    proportional to w * D^2 — the weighted-dataset semantics where a
    point of weight m behaves like m unit-weight duplicates (the exact
    distribution; individual draws differ because the sample space
    collapses m duplicates into one index). ``weights=None`` keeps the
    seed's original program — uniform first draw via randint, plain D^2
    after — so existing fits stay bit-identical.

    The draws' device ops run under the ``kpynq/seed`` scope (the first
    draw's few eager ops fall in none).
    """
    n = points.shape[0]
    pts = points.astype(jnp.float32)
    key, sub = jax.random.split(key)
    if weights is None:
        first_idx = jax.random.randint(sub, (), 0, n)
        w = None
    else:
        w = jnp.maximum(jnp.asarray(weights, jnp.float32), 0.0)
        wp = jnp.where(jnp.sum(w) > 0, w, jnp.ones_like(w))
        first_idx = jax.random.categorical(sub, jnp.log(wp + 1e-30))
    first = pts[first_idx]
    centroids = jnp.zeros((k, pts.shape[1]), jnp.float32).at[0].set(first)
    min_d2 = pairwise_sq_dists(pts, first[None])[:, 0]

    def body(i, carry):
        # the scope sits in the body: a loop called eagerly is traced
        # under a fresh name stack, which drops any scope around the call
        with jax.named_scope("kpynq/seed"):
            key, centroids, min_d2 = carry
            key, sub = jax.random.split(key)
            # Sample proportional to (w *) D^2 (guard the all-zero corner).
            scores = min_d2 if w is None else w * min_d2
            probs = jnp.where(jnp.sum(scores) > 0, scores,
                              jnp.ones_like(scores) if w is None else wp)
            idx = jax.random.categorical(sub, jnp.log(probs + 1e-30))
            c = pts[idx]
            centroids = centroids.at[i].set(c)
            d2 = pairwise_sq_dists(pts, c[None])[:, 0]
            return key, centroids, jnp.minimum(min_d2, d2)

    _, centroids, _ = jax.lax.fori_loop(1, k, body, (key, centroids, min_d2))
    return centroids
