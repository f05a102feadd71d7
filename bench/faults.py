"""Faults planted underneath a cell's timed path, for showing that its
check catches them (``tests/`` on the CPU, ``calibrate.py --fault`` on
the chip at the cell's own size). Each entry wraps one function of the
program, which the caller swaps in place and restores:

* fit (``engine.fit``, what ``KMeans.fit`` runs): ``unchanged`` returns
  its starting state (no iteration), ``half`` takes its means over every
  other point, ``altered`` changes one label.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


def _fit(kind):
    def wrap(real):
        def fit(points, init, **kw):
            if kind == "unchanged":
                return real(points, init, **{**kw, "max_iters": 0})
            if kind == "half":
                res, stats = real(points[::2], init, **kw)
                c = res.centroids
                d2 = (jnp.sum(points * points, 1)[:, None]
                      - 2 * points @ c.T + jnp.sum(c * c, 1)[None])
                return res._replace(
                    assignments=jnp.argmin(d2, 1).astype(jnp.int32)), stats
            res, stats = real(points, init, **kw)
            a, k = res.assignments, res.centroids.shape[0]
            return res._replace(assignments=a.at[0].set((a[0] + 1) % k)), stats
        return fit
    return wrap


FAULTS = {
    "fit": ("fit", {k: _fit(k) for k in ("unchanged", "half", "altered")}),
}


@contextlib.contextmanager
def planted(kind: str, fault: str):
    """Within the block, the traffic kind's timed path has ``fault``."""
    from repro.core import engine
    attr, table = FAULTS[kind]
    real = getattr(engine, attr)
    setattr(engine, attr, table[fault](real))
    try:
        yield
    finally:
        setattr(engine, attr, real)
