"""Traffic kind ``fit``: batch fits back to back.

A run fits ``instances`` problems, cycling through them. Every seed
gets the same work: the problems' point sets and k-means++ seeds come
from the mix's ``problem_seed``, and ``--seed`` draws, for each, new
coordinates of the same geometry (``data.signed_permutation``: its axes
reordered and some reversed, so every distance and the filter's work
stay as they were while every float the fit adds up is in another
order), and the order of the cycle. With the configurations' fixed
iteration counts (no convergence test) every fit runs the same number
of Lloyd iterations. Set-up makes the instances' points on the device
and runs one whole fit of each (every program the window runs is then
compiled or loaded). The window runs
``KMeans(engine=..., n_groups=G, max_iters, tol, seed).fit(points)``,
k-means++ seeding included, and closes at the end of the cycle through
the instances that crosses ``--seconds``: ``fit_s`` is the window over
the fits completed.

Check: every distinct fit of the window, in float64:

* ``label_gap``: the largest relative amount by which a point's label's
  centroid lies farther than its exact nearest returned centroid;
* ``inertia_rel_err``: the fit's inertia (from its labels and
  centroids) against that of the plain Lloyd of ``reference.py`` from
  the same k-means++ seeds at ``HIGHEST``, relative.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time

import jax
import numpy as np

import data
import reference as ref


@dataclasses.dataclass
class Fit:
    centroids: np.ndarray
    labels: np.ndarray
    n_iter: int
    distance_evals: float
    host_syncs: int
    backend: str


def program(cfg: dict, km_seed: int):
    """The system under test: one fit through the public estimator."""
    from repro.core import KMeans

    def fit(xd):
        km = KMeans(n_clusters=cfg["n_clusters"], algorithm="yinyang",
                    n_groups=cfg["n_groups"], engine=cfg["engine"],
                    max_iters=cfg["max_iters"], tol=cfg["tol"],
                    seed=km_seed).fit(xd)
        st = km.stats_
        return Fit(np.asarray(km.cluster_centers_), np.asarray(km.labels_),
                   km.n_iter_, km.distance_evals_,
                   st.host_syncs if st else 0, st.backend if st else "")
    return fit


def control(cfg: dict, km_seed: int):
    """The reference in the program's place, its cross terms one
    precision below the configuration's."""
    k = cfg["n_clusters"]

    def fit(xd):
        init = ref.kmeans_plusplus(jax.random.PRNGKey(km_seed), xd, k)
        c, a, it = ref.lloyd(xd, init, max_iters=cfg["max_iters"],
                             tol=cfg["tol"], precision="bf16_3x")
        c, a, it = jax.device_get((c, a, it))
        n = xd.shape[0]
        return Fit(np.asarray(c), np.asarray(a), int(it),
                   float(n) * k * (int(it) + 1), 0, "reference-bf16_3x")
    return fit


@dataclasses.dataclass
class Instance:
    xd: object                  # the points, on the device
    km_seed: int
    fit: object
    points: np.ndarray | None = None    # host copy, made for the check


def setup(cell) -> dict:
    cfg, tr = cell.config, cell.traffic
    m = tr["instances"]
    base = data.seeds(tr["problem_seed"], 2 * m)   # the same for every seed
    views = cell.seeds(m + 1)
    insts = []
    for j in range(m):
        xd, _ = data.blobs(cfg["n_points"], cfg["n_dims"], cfg["n_clusters"],
                           base[2 * j], cluster_std=cfg["cluster_std"],
                           spread=cfg["spread"])
        xd = data.signed_permutation(xd, views[j])
        fit = (control if cell.control else program)(cfg, base[2 * j + 1])
        fit(xd)                               # compile or load, and warm
        insts.append(Instance(xd, base[2 * j + 1], fit))
    first = views[m] % m
    return {"cfg": cfg, "order": insts[first:] + insts[:first]}


def window(state: dict, seconds: float, span) -> dict:
    order = state["order"]
    fits = []
    t0 = time.perf_counter()
    while True:
        for inst in order:
            with span("bench.fit"):
                fits.append((inst, inst.fit(inst.xd)))     # ends on the host
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    n, k = state["cfg"]["n_points"], state["cfg"]["n_clusters"]
    done = [f for _, f in fits]
    return {
        "e2e": {"fit_s": elapsed / len(fits)},
        "counters": {"fits": len(fits),
                     "iterations": sum(f.n_iter for f in done),
                     "distance_evals": sum(f.distance_evals for f in done),
                     "dense_evals": sum(float(n) * k * f.n_iter for f in done),
                     "host_syncs": sum(f.host_syncs for f in done),
                     "n_points": n, "n_clusters": k},
        "attempted": len(fits), "failed": 0, "fits": fits,
        "info": {"backend": done[-1].backend, "fits": len(fits),
                 "n_iter": [f.n_iter for f in done[:len(order)]],
                 "host_syncs": done[-1].host_syncs},
    }


def _key(f: Fit) -> str:
    h = hashlib.sha256(f.centroids.tobytes())
    h.update(np.ascontiguousarray(f.labels).tobytes())
    return h.hexdigest()


def check(state: dict, outcome: dict) -> dict:
    cfg = state["cfg"]
    refs = {}
    for inst in state["order"]:
        inst.points = np.asarray(jax.device_get(inst.xd))
        inst.xd.delete()                    # the program's state goes
        inst.fit = None
    for inst in state["order"]:
        xd = jax.device_put(inst.points)
        init = ref.kmeans_plusplus(jax.random.PRNGKey(inst.km_seed), xd,
                                   cfg["n_clusters"])
        c_ref, a_ref, it_ref = jax.device_get(ref.lloyd(
            xd, init, max_iters=cfg["max_iters"], tol=cfg["tol"]))
        xd.delete()
        own_gap = float(np.max(ref.label_gaps(inst.points, a_ref, c_ref)))
        refs[id(inst)] = (ref.inertia(inst.points, a_ref, c_ref), int(it_ref),
                          a_ref, own_gap)
    worst = {"label_gap": 0.0, "inertia_rel_err": 0.0}
    seen = {}
    for inst, f in outcome["fits"]:         # identical fits are judged once
        key = _key(f)
        if key not in seen:
            pts, (i_ref, _, a_ref, _) = inst.points, refs[id(inst)]
            gap = float(np.max(ref.label_gaps(pts, f.labels, f.centroids)))
            seen[key] = {
                "label_gap": gap,
                "inertia_rel_err": ref.NOT_A_CENTROID
                if gap >= ref.NOT_A_CENTROID else
                abs(ref.inertia(pts, f.labels, f.centroids) - i_ref) / i_ref,
                "labels_off_reference": int(np.sum(np.asarray(f.labels)
                                                   != a_ref))}
        for k in worst:
            worst[k] = max(worst[k], seen[key][k])
    outcome["info"].update(
        reference_iters=[r[1] for r in refs.values()],
        distinct_fits=len(seen),
        per_fit={k: [v[k] for v in seen.values()] for k in
                 ("label_gap", "inertia_rel_err", "labels_off_reference")},
        # the reference's own labels against its own centroids: the gap
        # that float32 at HIGHEST leaves in a plain Lloyd
        reference_label_gaps=[r[3] for r in refs.values()])
    return worst
