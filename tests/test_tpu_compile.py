"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles a kernel at the shapes the
exploration pipeline uses on the chip, against a v5e topology that is
described, not attached.  That catches what interpret mode cannot — a
block that breaks TPU tiling, a scalar read from VMEM, more VMEM or SMEM
than a kernel may use — without a chip.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.  Keep all such compiles in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import batch as B
from repro.core.circuits import benchmark_suite
from repro.core.sram import TOPOLOGY_LIBRARY, ModelTable
from repro.core.transforms import enumerate_recipes
from repro.kernels import aig_sim, cim_logic
from repro.runtime import jax_env


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out of the cache.
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - needs the TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def default_n_pad():
    """Padded node count of the largest default-scale circuit."""
    n = max(a.n_nodes for a in benchmark_suite(scale="default").values())
    return aig_sim._next_pow2(n + 1)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fused_suite(table_sharding, param_sharding, n_variants: int):
    """Compile the fused evaluate+select suite kernel for 9 circuits x
    ``n_variants`` x 12 topologies x 65 recipes, with its float64
    operands: the model operands on ``param_sharding``, the rest on
    ``table_sharding``."""
    n_c, n_r, n_l = 9, len(enumerate_recipes()) + 1, 4 * B.LEVEL_PAD
    assert n_r == 65
    topos = B.TopologyTable.from_topologies(TOPOLOGY_LIBRARY)
    n_t = len(topos)
    params = B._model_params(
        ModelTable.monte_carlo(n=n_variants, sigma=0.1, seed=0))
    with jax_env.x64():
        def spec(a, sharding=table_sharding):
            return _spec(sharding, np.shape(a), jnp.asarray(a).dtype)

        args = (
            _spec(table_sharding, (n_c, n_r, n_l, 3), jnp.int32),
            _spec(table_sharding, (n_c, n_r), jnp.int32),
            *map(spec, (
                topos.ops_per_cycle, topos.macros_per_type, topos.is_single,
                topos.total_bits, topos.rows, topos.cols,
            )),
            jax.tree.map(lambda a: spec(a, param_sharding), params),
            _spec(table_sharding, (n_c, n_t), jnp.bool_),
            _spec(table_sharding, (), jnp.float64),
        )
        fused_suite = B._fused_program()
        return fused_suite.lower(
            *args, discipline="list", mode="physical", use_latency=True
        ).compile()


def test_fused_suite_compiles_for_v5e(one_chip):
    """The 9 circuits x 16 variants x 12 topologies x 65 recipes fused
    evaluate+select kernel, with its float64 operands."""
    compiled = _compile_fused_suite(one_chip, one_chip, 16)
    assert compiled.as_text()


def test_sharded_fused_suite_compiles_for_v5e_2x2(topo):
    """The four-chip cell's kernel: 1,024 variants laid 256 a chip over
    the 2x2 mesh as `batch._shard_variants` lays them, every other
    operand replicated.  GSPMD partitions it along the variants, with
    the all-reduce that hands each chip the nominal (variant-0) winner."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(topo.devices), ("variants",))
    compiled = _compile_fused_suite(
        NamedSharding(mesh, PartitionSpec()),
        NamedSharding(mesh, PartitionSpec("variants")), 1024)
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("w", [w for _, w in aig_sim._TIERS])
def test_aig_pallas_compiles_for_v5e(one_chip, default_n_pad, w):
    """The Pallas characterization kernel at each word tier, over the
    largest default-scale circuit's whole-graph scratch."""
    assert aig_sim._pallas_fits(default_n_pad)
    width, qb = aig_sim._pallas_geometry(w)
    n_slots = aig_sim._n_pin_slots(w)
    n_blocks = aig_sim._CHUNK[w] // qb
    n_roots = 2
    i32 = jnp.int32
    fn = aig_sim._make_pallas_eval(interpret=False)
    compiled = fn.lower(
        _spec(one_chip, (1,), i32),
        _spec(one_chip, (2 * default_n_pad,), i32),
        _spec(one_chip, (n_blocks * n_slots,), i32),
        _spec(one_chip, (n_blocks * qb * n_roots,), i32),
        _spec(one_chip, (n_blocks * n_slots, width), i32),
        _spec(one_chip, (n_blocks * n_slots, width), i32),
        w=w, n_roots=n_roots,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cim_pallas_compiles_for_v5e(one_chip):
    """The CiM bit-plane kernel at a 128-lane block."""
    n_gates, n_pos, n_rows = 4096, 64, 512
    compiled = cim_logic._cim_call(False).lower(
        _spec(one_chip, (n_gates + n_pos, 4), jnp.int32),
        _spec(one_chip, (n_rows, 4 * cim_logic.LANE), jnp.int32),
        n_rows=n_rows, n_gates=n_gates, n_pos=n_pos,
        block_words=cim_logic.LANE,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
