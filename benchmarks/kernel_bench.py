"""Kernel microbench.

interpret=True timings are meaningless (Python emulation), so this
benchmark reports (a) XLA-path wall time of the same math — the oracle
the kernels were validated against — and (b) the ANALYTIC effect of
block-skip on the Pallas kernel: MXU FLOPs and HBM bytes at measured
block densities vs the dense kernel, from the BlockSpec tiling model:

  per live block: tile_n*tile_k*(2*D) MXU flops,
                  (tile_n*D + tile_k*D + tile_n*tile_k)*dtype bytes
  skipped block:  1 SMEM scalar read.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.ref import centroid_update_ref, pairwise_sq_dists_ref


def _time(fn, *args, repeats=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def block_skip_model(n, d, k, density, tile_n=256, tile_k=128,
                     dtype_bytes=4):
    gn, gk = -(-n // tile_n), -(-k // tile_k)
    live = gn * gk * density
    flops_dense = gn * gk * (tile_n * tile_k * 2 * d)
    flops_skip = live * (tile_n * tile_k * 2 * d)
    bytes_dense = gn * gk * (tile_n * d + tile_k * d +
                             tile_n * tile_k) * dtype_bytes
    bytes_skip = live * (tile_n * d + tile_k * d +
                         tile_n * tile_k) * dtype_bytes
    return {"flops_saving": flops_dense / max(flops_skip, 1),
            "bytes_saving": bytes_dense / max(bytes_skip, 1)}


def main():
    print("name,us_per_call,derived")
    key = jax.random.PRNGKey(0)
    for (n, d, k) in [(32768, 32, 128), (131072, 64, 256)]:
        x = jax.random.normal(key, (n, d))
        c = jax.random.normal(key, (k, d))
        f = jax.jit(pairwise_sq_dists_ref)
        t = _time(f, x, c)
        gflops = 2 * n * d * k / t / 1e9
        print(f"kernel/pairwise_dist_{n}x{d}x{k},{t * 1e6:.0f},"
              f"xla_cpu={gflops:.1f}GFLOP/s")
        a = jax.random.randint(key, (n,), 0, k)
        g = jax.jit(lambda xx, aa: centroid_update_ref(xx, aa, k))
        t = _time(g, x, a)
        print(f"kernel/centroid_update_{n}x{d}x{k},{t * 1e6:.0f},"
              f"xla_cpu_onehot_matmul")
    # analytic block-skip savings at the measured steady-state density
    for density in (0.1, 0.25, 0.5):
        m = block_skip_model(131072, 64, 256, density)
        print(f"kernel/block_skip_model_density{density},,"
              f"flops_saving={m['flops_saving']:.1f}x "
              f"bytes_saving={m['bytes_saving']:.1f}x")


if __name__ == "__main__":
    from repro.platform import use_compile_cache
    use_compile_cache()
    main()
