"""The engine's Pallas kernels compile for a TPU v5e, at real widths.

Nothing here runs on a chip: each test lowers a kernel through its
dispatch entry point for a *described* v5e (``topologies.
get_topology_desc``) and compiles it with the TPU compiler, which refuses
what interpret mode accepts -- tile-misaligned blocks, layouts Mosaic
cannot verify, too much VMEM, kernels it cannot partition.  Shapes are the
``chip_smoke.py`` deployments at the F-IVM paper's batch of 1,024 tuples:
the housing 2^22-postcode views (d = 1) and the retailer degree-m cofactor
planes (m = 10, d = 111).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import ring_fused, scatter_ops

B = 1024
DEGREE_M = ("degree", 10)
D_DEGREE = ring_fused.spec_width(DEGREE_M)  # 1 + m + m^2 = 111
HOUSING_PC = 1 << 22


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile ``fn`` for the shapes' devices; returns the HLO text."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("backend,S,d", [
    ("onehot", 8192, 1),
    ("onehot", 8192, D_DEGREE),
    ("onehot_dedup", 8192, 1),
    ("onehot_dedup", 8192, D_DEGREE),
    ("compact", HOUSING_PC, 1),
    ("compact", 65536, D_DEGREE),
])
def test_scatter_compiles_for_v5e(one_chip, backend, S, d):
    fn = functools.partial(scatter_ops._scatter_add_flat, backend=backend,
                           block_s=128, block_d=128, block_k=512)
    hlo = _compile(fn, _sds(one_chip, (S, d)),
                   _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B, d)))
    assert "tpu_custom_call" in hlo


def test_gather_mul_scatter_compiles_for_v5e(one_chip):
    Sg = scatter_ops.MAX_FUSED_SRC
    fn = functools.partial(scatter_ops._gather_mul_scatter_flat,
                           backend="onehot", block_s=128, block_d=128,
                           block_k=256)
    hlo = _compile(fn, _sds(one_chip, (8192, 1)),
                   _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (Sg, 1)),
                   _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B,)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("spec,S,src_rows", [
    # housing House chain: lift h2 (8 rows) into the 2^22-postcode view
    (("scalar",), HOUSING_PC, (8,)),
    # retailer chains: a sibling view plane and a lift, into a keyed view
    (DEGREE_M, 1024, (1024, 32)),
    (DEGREE_M, 1, (32, 32, 16)),
])
def test_fused_chain_compiles_for_v5e(one_chip, spec, S, src_rows):
    d = ring_fused.spec_width(spec)

    def fn(view, out_ids, vals, *planes_and_ids):
        n = len(planes_and_ids) // 2
        sources = list(zip(planes_and_ids[:n], planes_and_ids[n:]))
        return ring_fused.fused_apply(view, out_ids, vals, sources, spec,
                                      backend="fused_pallas")

    planes = [_sds(one_chip, (r, d)) for r in src_rows]
    ids = [_sds(one_chip, (B,), jnp.int32) for _ in src_rows]
    hlo = _compile(fn, _sds(one_chip, (S, d)),
                   _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B, d)),
                   *planes, *ids)
    assert "tpu_custom_call" in hlo


def test_compact_house_chain_compiles_for_v5e(one_chip):
    """The House chain (lift h2 of 8 rows into the 2^22-postcode view)
    through the compact ⊎: the kernel's grid spans the batch, not the
    view, so no [S, 128] plane is padded, swept or sliced; the program's
    temporaries stay far under the sweep's (4.0 GiB)."""
    spec = ("scalar",)

    def fn(view, out_ids, vals, lift, lift_ids):
        return ring_fused.fused_apply(view, out_ids, vals, [(lift, lift_ids)],
                                      spec, backend="fused_compact")

    compiled = jax.jit(fn, donate_argnums=0).lower(
        _sds(one_chip, (HOUSING_PC, 1)), _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B, 1)), _sds(one_chip, (8, 1)),
        _sds(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("backend", ["fused_pallas", "fused_compact"])
def test_kernels_compile_on_a_sharded_v5e_mesh(topo, backend):
    """A view split over a 2x2 mesh, as the plan-sharded stream executor
    places it: under the mesh each Mosaic kernel runs per device
    (``ring_scatter.per_device``); JAX refuses to partition one itself."""
    from repro.core.shard import make_mesh

    mesh = make_mesh(topo.devices)
    split = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    rep = NamedSharding(mesh, PartitionSpec())

    def fn(view, ids, vals, lift, lift_ids):
        a = scatter_ops._scatter_add_flat(view, ids, vals, backend="onehot",
                                          block_s=128, block_d=128,
                                          block_k=512)
        return ring_fused.fused_apply(a, ids, vals, [(lift, lift_ids)],
                                      ("scalar",), backend=backend)

    shapes = (_sds(split, (8192, 1)), _sds(rep, (B,), jnp.int32),
              _sds(rep, (B, 1)), _sds(rep, (8, 1)),
              _sds(rep, (B,), jnp.int32))
    with jax.set_mesh(mesh):
        hlo = _compile(fn, *shapes)
    assert hlo.count("tpu_custom_call") >= 2
