"""Main-path kernels compiled for a described TPU v5e, without a chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached, so what the v5e compiler would refuse (scoped
VMEM overruns, unaligned tiles, unpartitionable programs) fails here at
no chip time.  Nothing runs: these tests say nothing about results or
speed.  The topology is described only inside the module fixture below
(a libtpu load at import time would lock every other test worker out),
and the fixture skips where it cannot be described.
"""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a described-device compile can be written to the persistent
    # cache but never read back without a chip: keep the cache out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(arrays, sharding):
    import jax

    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in arrays]


# (C, V, B) at the extremes the TPU tier admits: the widest V at its
# lane cap (MAX_LANE_CELLS // V) and the tallest C at its V.  V=8192
# and V=16384 overran the 16 MiB default scoped VMEM before the sweep
# asked for its own limit.
@pytest.mark.parametrize("C, V, B", [
    (8192, 8192, 32),
    (4096, 16384, 16),
    (131072, 512, 64),
])
@pytest.mark.parametrize("scores", [False, True])
def test_dense_sweep_compiles_at_tier_caps(one_chip, C, V, B, scores):
    import jax
    import jax.numpy as jnp

    from mythril_tpu.ops import pallas_prop as PP

    assert PP.DenseClausePool.fits(C, V - 1, tpu=True)
    assert B == max(8, min(PP.MAX_LANES, PP.MAX_LANE_CELLS // V))
    sweep = PP._make_dpll_sweep(C, V, B, PP._tile_c(C, V), False, scores)
    args = [jax.ShapeDtypeStruct((C, V), jnp.bfloat16),
            jax.ShapeDtypeStruct((C, V), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
            jax.ShapeDtypeStruct((B, V), jnp.float32)]
    compiled = jax.jit(sweep).lower(*_shapes(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_resident_kernel_compiles_at_cone_gather_caps(one_chip):
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.ops import resident as RK
    from mythril_tpu.ops.frontier import frontier_deg

    C, num_vars, B = BS.MAX_CONE_GATHER_CLAUSES, BS.MAX_CONE_GATHER_VARS, 64
    V1 = num_vars + 1
    state = RK.resident_state0(np.ones((B, V1), np.int8), B,
                               BS.GATHER_DECISIONS,
                               width=BS.MAX_CLAUSE_WIDTH)
    args = [np.zeros((C, BS.MAX_CLAUSE_WIDTH), np.int32),
            np.zeros((V1, frontier_deg()), np.int32)]
    args += [state[k] for k in RK.RESIDENT_STATE_FIELDS]
    fn = RK.make_resident_step(num_vars, BS.GATHER_DECISIONS)
    fn.lower(*_shapes(args, one_chip)).compile()


def test_mesh_solve_compiles_on_four_chips_dp2_cp2(topo):
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.parallel.mesh import make_sharded_solve

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "cp"))
    num_vars, C, B = BS.MAX_CONE_GATHER_VARS, BS.MAX_CONE_GATHER_CLAUSES, 64
    lits = jax.ShapeDtypeStruct((C, BS.MAX_CLAUSE_WIDTH), np.int32,
                                sharding=NamedSharding(mesh, P("cp", None)))
    assign = jax.ShapeDtypeStruct((B, num_vars + 1), np.int8,
                                  sharding=NamedSharding(mesh, P("dp", None)))
    compiled = make_sharded_solve(mesh, num_vars).lower(
        lits, assign
    ).compile()
    assert "all-reduce" in compiled.as_text()  # the psum over cp
