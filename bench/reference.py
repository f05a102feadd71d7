"""Plain references the benchmark judges the program by.

Nothing here imports the program. ``kmeans_plusplus`` and ``lloyd`` are
written out from the textbook definitions (the seeding copies the
arithmetic of the program's k-means++ so both sides start from the same
centroids; the loop is the ``reference_lloyd`` of ``chip_smoke.py``);
``nearest`` and ``gaps`` are the nearest-centroid oracle, exact to
float64.

``cross`` is the one place a distance cross term ``x . c`` is formed.
``"highest"`` is what every configuration states (float32 at
``Precision.HIGHEST``); ``"bf16_3x"`` is the precision control: the
three-pass bfloat16 product of the TPU's ``Precision.HIGH``
(``hi*hi + hi*lo + lo*hi``), written out with explicit hi/lo splits so
that it means the same on the CPU, where ``HIGH`` is exact. The splits
round to bfloat16 by integer arithmetic on the bits rather than by a
conversion: on the TPU the compiler may drop a float32 -> bfloat16 ->
float32 round trip (excess precision), which would leave the low parts
counted twice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "bf16_3x")
NOT_A_CENTROID = 1e30        # the gap of a label that names no centroid


def _bf16_part(a):
    """``a`` rounded (to nearest, ties to even) to bfloat16 and held in
    float32, by integer arithmetic on its bits: exact, and no conversion
    that a compiler allowed excess precision could drop."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split_bf16(a):
    hi = _bf16_part(a)
    return hi, _bf16_part(a - hi)


def cross(x, c, precision: str):
    """``x @ c.T`` in float32 at the named precision."""
    if precision == "highest":
        return jnp.dot(x, c.T, precision=HI)
    if precision == "bf16_3x":
        xh, xl = _split_bf16(x)
        ch, cl = _split_bf16(c)
        return (jnp.dot(xh, ch.T, precision=HI) + jnp.dot(xh, cl.T, precision=HI)
                + jnp.dot(xl, ch.T, precision=HI))
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def sq_dists(x, c, precision: str = "highest"):
    """Squared distances ``||x||^2 - 2 x.c + ||c||^2``, floored at 0."""
    x2 = jnp.sum(x * x, axis=1)
    c2 = jnp.sum(c * c, axis=1)
    return jnp.maximum(x2[:, None] - 2.0 * cross(x, c, precision)
                       + c2[None, :], 0.0)


@functools.partial(jax.jit, static_argnames=("k",))
def kmeans_plusplus(key, points, k: int):
    """k-means++ seeding (Arthur & Vassilvitskii): a uniform first draw,
    then each draw proportional to D^2. The draws repeat the program's
    seeding for the same key, so a fit and its reference start from the
    same centroids."""
    n = points.shape[0]
    pts = points.astype(jnp.float32)
    key, sub = jax.random.split(key)
    first = pts[jax.random.randint(sub, (), 0, n)]
    centroids = jnp.zeros((k, pts.shape[1]), jnp.float32).at[0].set(first)
    min_d2 = sq_dists(pts, first[None])[:, 0]

    def body(i, carry):
        key, centroids, min_d2 = carry
        key, sub = jax.random.split(key)
        probs = jnp.where(jnp.sum(min_d2) > 0, min_d2, jnp.ones_like(min_d2))
        c = pts[jax.random.categorical(sub, jnp.log(probs + 1e-30))]
        centroids = centroids.at[i].set(c)
        return key, centroids, jnp.minimum(min_d2, sq_dists(pts, c[None])[:, 0])

    _, centroids, _ = jax.lax.fori_loop(1, k, body, (key, centroids, min_d2))
    return centroids


def _assign(x, c, precision):
    """argmin over centroids, in row blocks so no (N, K) matrix of the
    whole set is live at once; a ragged last block is padded with zero
    rows, whose labels are dropped."""
    n, d = x.shape
    block = min(8192, n)
    xb = jnp.pad(x, ((0, (-n) % block), (0, 0))).reshape(-1, block, d)
    return jax.lax.map(
        lambda t: jnp.argmin(sq_dists(t, c, precision), axis=1), xb
    ).reshape(-1)[:n].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_iters", "tol", "precision"))
def lloyd(x, c0, *, max_iters: int, tol: float, precision: str = "highest"):
    """Plain float32 Lloyd: assign by argmin, centroids to the means
    (an empty cluster keeps its centroid), stop when no centroid moved
    more than ``tol`` or after ``max_iters`` moves. Returns
    ``(centroids, labels, moves)`` with the labels of a last assignment
    against the returned centroids — what ``KMeans.labels_`` means."""
    k = c0.shape[0]

    def body(state):
        i, c, _ = state
        a = _assign(x, c, precision)
        sums = jax.ops.segment_sum(x, a, num_segments=k)
        cnt = jax.ops.segment_sum(jnp.ones(x.shape[0], jnp.float32), a,
                                  num_segments=k)
        new = jnp.where(cnt[:, None] > 0,
                        sums / jnp.maximum(cnt, 1.0)[:, None], c)
        shift = jnp.max(jnp.sqrt(jnp.sum((new - c) ** 2, axis=1)))
        return i + 1, new, shift

    def cond(state):
        i, _, shift = state
        return jnp.logical_and(i < max_iters, shift > tol)

    i, c, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), c0.astype(jnp.float32),
                     jnp.float32(jnp.inf)))
    return c, _assign(x, c, precision), i


@jax.jit
def _screen(q, c):
    """Per query, at float32 ``HIGHEST``: the smallest and second
    smallest squared distance over all centroids, and the argmin."""
    d2 = sq_dists(q, c)
    arg = jnp.argmin(d2, axis=1).astype(jnp.int32)
    lo = jnp.min(d2, axis=1)
    second = jnp.min(jnp.where(jnp.arange(c.shape[0])[None, :] == arg[:, None],
                               jnp.inf, d2), axis=1)
    return lo, second, arg


def exact_d2(q, c):
    diff = q.astype(np.float64) - c.astype(np.float64)
    return np.einsum("nd,nd->n", diff, diff)


def nearest(q, c, *, block: int = 32768):
    """Each query's nearest centroid and its squared distance, exact:
    ``(index (n,), d2 (n,) float64)``.

    A float32 screen on the device finds each query's nearest centroid;
    wherever the first two are closer than 1e-4 of ``||q||^2 + ||c||^2``
    (a thousand times the screen's own rounding), every centroid is
    measured again in float64 on the host, so the answer does not rest
    on float32 at all."""
    q = np.asarray(q, np.float32)
    c = np.asarray(c, np.float32)
    n = len(q)
    cd = jnp.asarray(c)
    block = max(min(block, n), 1)
    pad = (-n) % block                      # one shape: one program
    qp = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
    parts = [jax.device_get(_screen(jnp.asarray(qp[lo:lo + block]), cd))
             for lo in range(0, n + pad, block)]
    lo_d2, second, arg = (np.concatenate(p)[:n] for p in zip(*parts))
    scale = np.einsum("nd,nd->n", q, q) + float(np.max(np.sum(c * c, axis=1)))
    idx = np.nonzero((second - lo_d2) <= 1e-4 * scale)[0]
    best = arg.astype(np.int64)
    c64 = c.astype(np.float64)
    c2 = np.sum(c64 * c64, axis=1)
    for s in range(0, len(idx), 4096):
        sel = idx[s:s + 4096]
        qs = q[sel].astype(np.float64)
        # float64 ranks within ~1e-12 of the scale
        best[sel] = np.argmin(np.sum(qs * qs, axis=1)[:, None]
                              - 2.0 * qs @ c64.T + c2[None, :], axis=1)
    return best, exact_d2(q, c[best])


def gaps(q, labels, c, best, best_d2) -> np.ndarray:
    """Per query, how much farther its label's centroid lies than the
    nearest one, ``(d2(q, c[label]) - d2_min) / d2_min`` in float64 (0
    where the label is a nearest centroid), given :func:`nearest`'s
    answer. A label that names no centroid reads ``NOT_A_CENTROID``."""
    labels = np.asarray(labels)
    n = len(q)
    if labels.shape != (n,) or (n and (labels.min() < 0
                                      or labels.max() >= len(c))):
        return np.full((max(n, 1),), NOT_A_CENTROID)
    out = np.zeros((max(n, 1),), np.float64)
    off = np.nonzero(labels != best)[0]
    if len(off):
        own = exact_d2(np.asarray(q)[off], np.asarray(c)[labels[off]])
        bd = np.minimum(best_d2[off], own)
        out[off] = (own - bd) / np.maximum(bd, np.finfo(np.float64).tiny)
    return out


def label_gaps(q, labels, c) -> np.ndarray:
    """:func:`gaps` of each query's label against the exact nearest."""
    labels = np.asarray(labels)
    if labels.shape != (len(q),) or not len(q):
        return gaps(q, labels, c, None, None)
    best, best_d2 = nearest(q, c)
    return gaps(q, labels, c, best, best_d2)


def inertia(x, labels, c) -> float:
    """Sum of squared distances of the points to their labels'
    centroids, in float64."""
    x = np.asarray(x)
    total = 0.0
    for lo in range(0, len(x), 1 << 18):
        total += float(np.sum(exact_d2(x[lo:lo + (1 << 18)],
                                        np.asarray(c)[np.asarray(labels)[lo:lo + (1 << 18)]])))
    return total
