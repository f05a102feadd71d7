"""Pure-jnp oracles (the correctness ground truth).

Each function mirrors one kernel in this package, or the core
primitive every driver calls (``pairwise_sq_dists_ref`` for
``core.distances.pairwise_sq_dists``, ``centroid_update_ref`` for
``core.kmeans.centroid_sums`` / ``onehot_centroid_sums``), with the
same output semantics; tests sweep shapes/dtypes and
``assert_allclose`` implementation-vs-oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_sq_dists_ref(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """(N, D), (K, D) -> (N, K) squared distances, fp32 accumulate."""
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)
    c2 = jnp.sum(cf * cf, axis=-1)
    return jnp.maximum(x2 - 2.0 * (xf @ cf.T) + c2[None, :], 0.0)


def grouped_assign_ref(x, c_grouped, ids, block_mask, tile_n: int):
    """Oracle for the group-granular block-skip kernel.

    Mirrors ``grouped_assign``: per (point, group) returns
    (min, argmin-id, second-min) of squared distances over the group's
    valid slots, +inf/-1 for skipped blocks and padded slots; global
    (best, idx) reduced over live groups only.
    """
    n = x.shape[0]
    g, lmax, _ = c_grouped.shape
    live = jnp.repeat(jnp.asarray(block_mask, bool), tile_n, axis=0)[:n]
    d2 = pairwise_sq_dists_ref(
        x, c_grouped.reshape(g * lmax, -1)).reshape(n, g, lmax)
    d2 = jnp.where((ids >= 0)[None], d2, jnp.inf)
    d2 = jnp.where(live[:, :, None], d2, jnp.inf)
    gmin = jnp.min(d2, axis=2)
    slot = jnp.argmin(d2, axis=2)
    garg = jnp.take_along_axis(jnp.broadcast_to(ids[None], d2.shape),
                               slot[..., None], 2)[..., 0]
    eye = slot[..., None] == jnp.arange(lmax)[None, None]
    gmin2 = jnp.min(jnp.where(eye, jnp.inf, d2), axis=2)
    best = jnp.min(gmin, axis=1)
    bg = jnp.argmin(gmin, axis=1)
    idx = jnp.where(jnp.isfinite(best),
                    jnp.take_along_axis(garg, bg[:, None], 1)[:, 0], -1)
    gmin = jnp.where(live, gmin, jnp.inf)
    garg = jnp.where(live, garg, -1)
    gmin2 = jnp.where(live, gmin2, jnp.inf)
    return (best, idx.astype(jnp.int32), gmin, garg.astype(jnp.int32),
            gmin2)


def centroid_update_ref(points: jnp.ndarray, assignments: jnp.ndarray,
                        k: int):
    """Segment sums + counts: (K, D) fp32 sums, (K,) fp32 counts."""
    onehot = jax.nn.one_hot(assignments, k, dtype=jnp.float32)
    return onehot.T @ points.astype(jnp.float32), jnp.sum(onehot, axis=0)


def flash_attention_ref(q, k, v):
    """Causal softmax attention oracle, (B, H, S, D) fp32 softmax."""
    import math
    b, h, s, d = q.shape
    sc = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ssd_intra_ref(c, b, x, cum):
    """Intra-chunk SSD oracle. c,b: (G,Q,N); x: (G,Q,P); cum: (G,Q)."""
    scores = jnp.einsum("gin,gjn->gij", c.astype(jnp.float32),
                        b.astype(jnp.float32))
    diff = cum[:, :, None] - cum[:, None, :]
    q = c.shape[1]
    mask = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(mask[None], jnp.exp(diff.astype(jnp.float32)), 0.0)
    return jnp.einsum("gij,gjp->gip", scores * decay,
                      x.astype(jnp.float32))
