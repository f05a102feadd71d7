import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

For every (arch × shape × mesh) cell: AOT ``jax.jit(...).lower(...)``
with explicit in/out shardings, ``.compile()``, then record
memory_analysis / cost_analysis / collective-bytes into a JSON cache
(results/dryrun/<arch>__<shape>__<mesh>.json).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both [--force]
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from ..configs import get_config, list_configs           # noqa: E402
from ..roofline.analysis import (collective_bytes_per_device,  # noqa: E402
                                 roofline)
from ..train.steps import (make_prefill_step, make_serve_step,  # noqa: E402
                           make_train_step)
from .mesh import make_production_mesh, n_chips          # noqa: E402
from .sharding import named                               # noqa: E402
from .specs import SHAPES, input_specs                    # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def analysis_cfg(cfg, seq: int, n_layers: int):
    """Variant for exact cost accounting: XLA's cost_analysis counts
    while-loop bodies ONCE (verified empirically), so the analysis
    artifact disables every inner scan (query/loss/SSD chunking) and is
    lowered at L=1 and L=2 — the diff is the exact per-layer cost, which
    scales analytically to the real depth. The deliverable artifact (A)
    keeps scan+chunking and proves compile + memory."""
    import dataclasses
    kw = dict(n_layers=n_layers, unroll_layers=True, unroll_chunks=True)
    return dataclasses.replace(cfg, **kw)


def corrected_cost(arch, shape, multi_pod, cfg):
    """(flops, bytes, collective-bytes) per device, trip-count-exact."""
    recs = []
    for L in (1, 2):
        lowered, mesh, c, kind = lower_cell(
            arch, shape, multi_pod,
            cfg_override=analysis_cfg(cfg, SHAPES[shape]["seq"], L))
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        coll = collective_bytes_per_device(compiled.as_text())
        recs.append({"flops": float(cost.get("flops", 0.0)),
                     "bytes": float(cost.get("bytes accessed", 0.0)),
                     "coll": float(coll["total"])})
    L = cfg.n_layers
    out = {}
    for k in ("flops", "bytes", "coll"):
        per_layer = recs[1][k] - recs[0][k]
        if per_layer < 0:
            # GSPMD occasionally picks different strategies for the L=1
            # and L=2 artifacts; a negative diff is accounting noise.
            # Clamp to the L=1 cost treated as 1 layer's worth.
            per_layer = recs[0][k] / 2
            out.setdefault("clamped", []).append(k)
        out[k] = recs[0][k] + (L - 1) * per_layer
        out[f"{k}_per_layer"] = per_layer
    return out


def lower_cell(arch: str, shape: str, multi_pod: bool, *,
               cfg_override=None):
    cfg = cfg_override or get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    args, in_specs, out_specs, kind = input_specs(cfg, shape, mesh)
    if kind == "train":
        step = make_train_step(cfg)
    elif kind == "prefill":
        step = make_prefill_step(cfg)
    else:
        step = make_serve_step(cfg)
    with mesh:
        jitted = jax.jit(step,
                         in_shardings=named(mesh, in_specs),
                         out_shardings=named(mesh, out_specs))
        lowered = jitted.lower(*args)
    return lowered, mesh, cfg, kind


def run_cell(arch: str, shape: str, multi_pod: bool, *, force=False,
             cfg_override=None, tag: str = "") -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    out_path = RESULTS / f"{arch}__{shape}__{mesh_name}{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "tag": tag}
    try:
        lowered, mesh, cfg, kind = lower_cell(arch, shape, multi_pod,
                                              cfg_override=cfg_override)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        cost = compiled.cost_analysis()
        try:
            mem = compiled.memory_analysis()
            mem_rec = {
                "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not implement it
            mem_rec = {"error": str(e)}
        hlo = compiled.as_text()
        coll = collective_bytes_per_device(hlo)
        info = SHAPES[shape]
        corr = corrected_cost(arch, shape, multi_pod, cfg)
        rl = roofline({"flops": corr["flops"],
                       "bytes accessed": corr["bytes"]},
                      corr["coll"], n_chips(mesh), cfg=cfg,
                      kind=kind, batch=info["batch"], seq=info["seq"])
        rec.update(ok=True, kind=kind, lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1),
                   cost_raw={k: cost.get(k) for k in
                             ("flops", "bytes accessed", "transcendentals")},
                   cost_corrected=corr,
                   memory=mem_rec, collectives=coll, roofline=rl,
                   hlo_bytes=len(hlo))
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    RESULTS.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1, default=str))
    status = "OK" if rec.get("ok") else f"FAIL ({rec.get('error', '')[:80]})"
    wall = time.time() - t0
    print(f"[dryrun] {arch:26s} {shape:12s} {mesh_name:8s} "
          f"{wall:6.1f}s  {status}", flush=True)
    return rec


def run_kmeans_cell(multi_pod: bool, *, force=False, tag: str = "",
                    compress: bool = False, opt_sq: bool = False) -> dict:
    """The paper's own workload on the production mesh: distributed
    filtered K-means with points sharded over every chip."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs.kpynq import production as prob
    from .mesh import batch_axes

    mesh_name = "2x16x16" if multi_pod else "16x16"
    out_path = RESULTS / f"kpynq-kmeans__fit__{mesh_name}{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    t0 = time.time()
    rec = {"arch": "kpynq-kmeans", "shape": "fit", "mesh": mesh_name,
           "tag": tag}
    try:
        from ..core.distributed import make_fit_sharded

        mesh = make_production_mesh(multi_pod=multi_pod)
        axes = batch_axes(mesh) + ("model",)   # points over EVERY axis
        n_groups = max(prob.k // 10, 1)
        fit = make_fit_sharded(mesh, axes, prob.k, n_groups,
                               prob.max_iters, prob.tol,
                               compress=compress, opt_sq=opt_sq)
        pts = jax.ShapeDtypeStruct(
            (prob.n_points, prob.n_dims), jnp.float32,
            sharding=NamedSharding(mesh, P(axes, None)))
        init = jax.ShapeDtypeStruct(
            (prob.k, prob.n_dims), jnp.float32,
            sharding=NamedSharding(mesh, P()))
        with mesh:
            lowered = jax.jit(fit).lower(pts, init)
            compiled = lowered.compile()
        # exact per-iteration accounting: XLA does not cost while bodies
        # (and, for this shard_map program, called computations either),
        # so lower 1- and 2-iteration unrolled variants, cost them from
        # the HLO TEXT, and diff
        from ..roofline.analysis import hlo_dot_flops, hlo_traffic_bytes
        recs = []
        for it in (1, 2):
            f_u = make_fit_sharded(mesh, axes, prob.k, n_groups,
                                   prob.max_iters, prob.tol,
                                   compress=compress, opt_sq=opt_sq,
                                   unroll_iters=it)
            with mesh:
                c_u = jax.jit(f_u).lower(pts, init).compile()
            txt_u = c_u.as_text()
            recs.append({
                "flops": hlo_dot_flops(txt_u, prob.n_dims),
                "bytes": hlo_traffic_bytes(txt_u),
                "coll": float(collective_bytes_per_device(
                    txt_u)["total"])})
        corr = {}
        for kk in ("flops", "bytes", "coll"):
            per_iter = recs[1][kk] - recs[0][kk]
            corr[kk] = recs[0][kk] + (prob.max_iters - 1) * per_iter
            corr[f"{kk}_per_iter"] = per_iter
        coll = collective_bytes_per_device(compiled.as_text())
        rl = roofline({"flops": corr["flops"],
                       "bytes accessed": corr["bytes"]},
                      corr["coll"], n_chips(mesh))
        # useful work: one dense assignment pass per iteration
        mf = (2.0 * prob.n_points * prob.k * prob.n_dims *
              prob.max_iters) / n_chips(mesh)
        rl["model_flops_per_device"] = mf
        rl["useful_flops_ratio"] = mf / corr["flops"] if corr["flops"] else 0
        t_star = max(rl["t_compute_s"], rl["t_memory_s"],
                     rl["t_collective_s"])
        rl["roofline_fraction"] = (mf / 197e12) / t_star if t_star else 0
        rec["cost_corrected"] = corr
        cost_a = compiled.cost_analysis()
        rec.update(ok=True, kind="kmeans",
                   compile_s=round(time.time() - t0, 1),
                   cost_raw={k: cost_a.get(k) for k in
                             ("flops", "bytes accessed")},
                   collectives=coll, roofline=rl)
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    RESULTS.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1, default=str))
    print(f"[dryrun] {'kpynq-kmeans':26s} {'fit':12s} {mesh_name:8s} "
          f"{time.time() - t0:6.1f}s  "
          f"{'OK' if rec.get('ok') else 'FAIL (' + rec.get('error', '')[:60] + ')'}",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list_configs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = 0
    if args.arch in ("all", "kpynq-kmeans"):
        for mp in meshes:
            rec = run_kmeans_cell(mp, force=args.force)
            n_ok += bool(rec.get("ok"))
            n_fail += not rec.get("ok")
        if args.arch == "kpynq-kmeans":
            print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
            raise SystemExit(1 if n_fail else 0)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, force=args.force)
                n_ok += bool(rec.get("ok"))
                n_fail += not rec.get("ok")
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
