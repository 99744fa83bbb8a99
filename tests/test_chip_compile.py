"""Compile the device path's Mosaic kernels for a described TPU v5e.

No chip is attached here: the TPU compiler builds each program for
devices that are described, not present, so what Mosaic would refuse on
the chip (unaligned slices, a VMEM claim over the limit, a kernel that
cannot be partitioned) fails here at no chip time. Sizes are the ones
`chip_smoke.py` runs: the 50k-pod x 5k-node synthetic snapshot, and its
4-way node split for the mesh rung.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and under pytest-xdist every worker
imports this file.
"""

import os

import numpy as np
import pytest

from kube_batch_tpu.testing import x64_enabled

PODS, NODES = 50_000, 5_000
MESH = 4


@pytest.fixture(scope="module")
def topo():
    """The v5e:2x2 topology, with the persistent compile cache off (an
    entry compiled for a described chip cannot be read back here) and
    the TPU compiler's logs off (they would land under /tmp)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 -- any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            with x64_enabled(False):  # the chip path solves in float32
                yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def arrays(topo):
    import __graft_entry__ as ge

    return ge._encoded_arrays(PODS, NODES, np.float32)


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype, sharding=sharding
        ),
        tree,
    )


def test_fused_solve_compiles(topo, arrays):
    from jax.sharding import SingleDeviceSharding

    from kube_batch_tpu.ops.pallas_solve import PallasSolver

    solver = PallasSolver(arrays, enable_drf=True, enable_proportion=True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = solver.fn.lower(*_shapes(solver.trace_args(), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_step_compiles_at_mesh_shard_shape(topo, arrays):
    import jax
    from jax.sharding import SingleDeviceSharding

    from kube_batch_tpu.ops import pallas_solve as ps

    nr_loc = -(-ps._rows(arrays["node_idle"].shape[0]) // MESH)
    gt = arrays["compat"].shape[0]
    i32, f32 = np.int32, np.float32
    block = (
        [((ps.IVEC_LEN,), i32), ((ps.FVEC_LEN,), f32)]
        + [((gt, nr_loc, ps.LANES), i32), ((gt, nr_loc, ps.LANES), f32)]
        + [((ps.R8, nr_loc, ps.LANES), f32)]  # nalloc
        + [((nr_loc, ps.LANES), i32)] * 3  # nmax, nihs, nrhs
        + [((ps.R8, nr_loc, ps.LANES), f32)] * 3  # idle, rel, used
        + [((nr_loc, ps.LANES), i32)] * 2  # ntasks, nports
    )
    one_chip = SingleDeviceSharding(topo.devices[0])
    step = ps._build_block_step(nr_loc, gt, False)
    compiled = (
        jax.jit(step)
        .lower(*[jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in block])
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_pallas_program_compiles_on_four_chips(topo, arrays):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from kube_batch_tpu.ops.pallas_solve import _ports_mask
    from kube_batch_tpu.parallel.sharded import AXIS_NAME
    from kube_batch_tpu.parallel.sharded_pallas import ShardedPallasSolver

    mesh = Mesh(np.asarray(topo.devices[:MESH]), (AXIS_NAME,))
    solver = ShardedPallasSolver(
        arrays, mesh, enable_drf=True, enable_proportion=True,
        block_impl="mosaic", exchange_batch=1,
    )
    a_call = dict(arrays, _tports=_ports_mask(np.asarray(arrays["task_ports"])))
    replicated = NamedSharding(mesh, P())
    compiled = solver._fresh.lower(
        _shapes(a_call, replicated), _shapes(solver._statics, replicated)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
