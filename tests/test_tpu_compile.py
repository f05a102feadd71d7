"""Compile the main path's TPU programs for a described v5e, no chip.

The TPU compiler is installed with jax and compiles for a topology that
is described rather than attached. What it refuses here (a block shape
off the (8, 128) tiling, more fast memory than a kernel may use, a
program that does not fit the device) it would refuse on the chip; what
it accepts says nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and each
xdist worker imports every test file. The persistent compilation cache
is off around these compiles (an entry written for a described chip
cannot be read back without one).
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.kpynq import paper_suite
from repro.core import engine as _engine
from repro.core.distributed import make_fit_sharded_engine
from repro.core.kmeans import onehot_centroid_sums
from repro.kernels import grouped_assign

# the module, which the package's function of the same name shadows
grouped_assign_mod = importlib.import_module("repro.kernels.grouped_assign")
LMAX = 24          # the largest centroid group the shapes below assume


def _problem(name):
    prob = next(p for p in paper_suite if p.name == name)
    return prob.n_points, prob.n_dims, prob.k, prob.n_groups or prob.k // 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding):
    """Shape-and-dtype specs placed on ``sharding``."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("name", ["uci-xlarge", "uci-wide"])
def test_grouped_assign_compiles_for_v5e(one_chip, name):
    """The block-skip kernel at a real width: every BlockSpec on the
    tiling, the mask in SMEM, and a Mosaic kernel in the program."""
    n, d, k, g = _problem(name)
    tile_n = 256
    s = _on(one_chip)
    fn = jax.jit(lambda x, c, ids, m, x2, c2: grouped_assign(
        x, c, ids, m, tile_n=tile_n, interpret=False, x2=x2, c2g=c2))
    compiled = fn.lower(
        s((n, d), jnp.float32), s((g, LMAX, d), jnp.float32),
        s((g, LMAX), jnp.int32), s((n // tile_n, g), jnp.bool_),
        s((n,), jnp.float32), s((g, LMAX), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,d,g,lmax,all_groups", [
    (262_144, 128, 102, 72, True),     # the IVF1024 cell
    (32_768, 128, 1_638, 72, False),   # K=16,384
    (2_458_285, 68, 5, LMAX, True),    # census1990-d68-k50: ragged N, D=68
])
def test_grouped_assign_group_loop_compiles_for_v5e(one_chip, n, d, g, lmax,
                                                    all_groups):
    """The kernel's in-step group loop with its resident centroid blocks
    at the size the groups-per-step rule gives: within the kernel's VMEM
    limit, and the whole program within one chip's 16 GB."""
    gs = grouped_assign_mod.groups_per_step(g, lmax, d, 256)
    assert (gs == g) is all_groups
    s = _on(one_chip)
    fn = jax.jit(lambda x, c, ids, m, x2, c2: grouped_assign(
        x, c, ids, m, tile_n=256, interpret=False, x2=x2, c2g=c2))
    compiled = fn.lower(
        s((n, d), jnp.float32), s((g, lmax, d), jnp.float32),
        s((g, lmax), jnp.int32), s((-(-n // 256), g), jnp.bool_),
        s((n,), jnp.float32), s((g, lmax), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 << 30


def _has_scatter(compiled):
    """Whether the program holds a scatter instruction (the opcode, not
    the word, which source names in the metadata may carry)."""
    return re.search(r"\sscatter\(", compiled.as_text()) is not None


def _compile_candidate_pass(sharding, n, d, k, g, lmax):
    """``engine.pallas_candidate_pass`` compiled at (N, D, K, G, Lmax)."""
    s = _on(sharding)
    fn = jax.jit(lambda *a: _engine.pallas_candidate_pass(
        *a[:9], n_groups=g, tile_n=256, interpret=False, x2=a[9],
        c2=a[10]))
    return fn.lower(
        s((n, d), jnp.float32), s((k, d), jnp.float32),
        s((n,), jnp.int32), s((n,), jnp.float32), s((n, g), jnp.float32),
        s((k,), jnp.int32), s((g, lmax), jnp.int32), s((g,), jnp.float32),
        s((n,), jnp.bool_), s((n,), jnp.float32),
        s((k,), jnp.float32)).compile()


def test_pallas_candidate_pass_compiles_for_v5e(one_chip):
    """The engine's pallas candidate pass (mask build, grouped centroid
    gather, kernel, lower-bound refresh) jitted at uci-xlarge."""
    n, d, k, g = _problem("uci-xlarge")
    compiled = _compile_candidate_pass(one_chip, n, d, k, g, LMAX)
    assert "tpu_custom_call" in compiled.as_text()
    assert not _has_scatter(compiled)
    # the (N, G) bounds and the kernel's outputs fit one 16 GB chip
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_census_candidate_pass_compiles_for_v5e(one_chip):
    """The engine's pallas candidate pass at census1990-d68-k50's shape:
    N = 2,458,285, 173 past a multiple of the tile (the points, their
    norms and the block mask are padded in every pass), D = 68 off the
    128 lanes, K = 50 in G = 5 groups; arguments, outputs and temporaries
    within one 16 GB chip; the lower-bound cap a select, no scatter."""
    compiled = _compile_candidate_pass(one_chip, 2_458_285, 68, 50, 5, LMAX)
    assert "tpu_custom_call" in compiled.as_text()
    assert not _has_scatter(compiled)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 << 30


def test_sift_candidate_pass_has_no_scatter_for_v5e(one_chip):
    """The engine's pallas candidate pass at sift128-ivf1024's shape
    (N = 262,144, D = 128, K = 1,024 in G = 102 groups of up to 72): the
    lower-bound cap fuses into the (N, G) select, no row-serial scatter
    is left in the program."""
    compiled = _compile_candidate_pass(one_chip, 262_144, 128, 1_024, 102,
                                       72)
    assert "tpu_custom_call" in compiled.as_text()
    assert not _has_scatter(compiled)


@pytest.mark.parametrize("n,d,k", [
    (2_458_285, 68, 50),               # census1990-d68-k50
    (262_144, 128, 1_024),             # sift128-ivf1024
])
def test_onehot_centroid_sums_compile_for_v5e(one_chip, n, d, k):
    """The centroid sums as the one-hot product, at both fit cells'
    shapes: no temporaries, so no per-call relayout of the points and
    no (N, K) one-hot is stored (the scatter-adds it replaces take 1.26
    GB at census)."""
    s = _on(one_chip)
    compiled = jax.jit(onehot_centroid_sums, static_argnums=2).lower(
        s((n, d), jnp.float32), s((n,), jnp.int32), k).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_serve_fused_assign_compiles_for_v5e(one_chip):
    """The default serve backend at a full 8,192-row bucket, K=256."""
    s = _on(one_chip)
    compiled = _engine.serve_assign_fused.lower(
        s((8192, 32), jnp.float32), s((256, 32), jnp.float32),
        s((256,), jnp.float32), chunk=1024).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_engine_fit_compiles_for_four_chips(topo):
    """The compact sharded fit (capacity ladder under shard_map, psum of
    the centroid sums) over a 4-chip mesh of the described devices."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    n, d, k, g = 65_536, 32, 256, 25

    def s(shape, dtype, spec):
        return _on(NamedSharding(mesh, spec))(shape, dtype)
    fn = jax.jit(make_fit_sharded_engine(mesh, ("data",), k, g, 50, 1e-4,
                                         shard_n=n // 4))
    compiled = fn.lower(
        s((n, d), jnp.float32, P("data", None)),
        s((n,), jnp.bool_, P("data")), s((k, d), jnp.float32, P()),
        s((k,), jnp.int32, P()), s((g, LMAX), jnp.int32, P()),
        s((g,), jnp.float32, P())).compile()
    assert "all-reduce" in compiled.as_text()
