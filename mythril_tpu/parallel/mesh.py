"""Sharded lockstep SAT propagation over a jax.sharding.Mesh.

Layout:
- ``dp`` axis: frontier lanes (assignment vectors) — pure data parallel.
- ``cp`` axis: the clause pool is sharded row-wise; each device scans
  its clause shard and the per-variable forced-literal vectors and
  conflict flags are combined with ``lax.psum`` over ``cp`` each BCP
  iteration.  This is the collective clause-exchange component from
  BASELINE.json: state that prunes one lane's search propagates to
  every chip holding part of the pool.

``dryrun_multichip`` in __graft_entry__.py builds this mesh on N virtual
devices and executes one full training-equivalent step (frontier
feasibility solve) end to end.
"""

import logging
from functools import partial
from typing import Tuple

import numpy as np

from mythril_tpu.observability import spans as obs

log = logging.getLogger(__name__)

# DPLL budgets for the sharded path: every sweep costs a psum over cp,
# so the step budget trades collective latency against search depth.
# Enough to complete full-pool assignments on dryrun/test-scale pools;
# production frontiers lean on the CDCL tail past this.
# Matches the dense tier's calibration (ops/pallas_prop.py): the
# captured scale-scenario dispatch (10.5k cone clauses, 8 lanes)
# completes in ~1.7-2k sweeps / ~700 decisions, so the old 1536-sweep
# budget bailed on exactly the frontiers the mesh exists for.  The
# while_loop exits early on decided batches — a budget is a cap, not a
# cost — so small dispatches don't pay for the headroom.
MAX_STEPS = 4096
MAX_DECISIONS = 1024


_mesh_cache = None
_solve_cache = {}


def reset_mesh_caches() -> None:
    """Drop the process-wide mesh and its jitted shard_map solves.

    Called from ``ops.batched_sat.reset_resident_pools`` (checkpoint
    resume, serve decontamination, tests): the mesh captures a device
    topology and ``_solve_cache`` keys on ``id(mesh)``, so keeping
    either across a resume could serve a solve compiled for a dead
    topology — or, worse, collide on a garbage-collected mesh whose id
    was recycled by a new one."""
    global _mesh_cache
    _mesh_cache = None
    _solve_cache.clear()


def get_mesh():
    """Process-wide default mesh over all visible devices (cached)."""
    global _mesh_cache
    if _mesh_cache is None:
        _mesh_cache = build_mesh()
    return _mesh_cache


def build_mesh(n_devices: int = None, dp: int = None, cp: int = None):
    """Build a dp x cp mesh over the available (or first n) devices."""
    import jax

    devices = jax.devices()[: n_devices or len(jax.devices())]
    count = len(devices)
    if dp is None or cp is None:
        # favor lane parallelism; clause sharding gets the rest
        dp = 1
        while dp * 2 <= count and (count // (dp * 2)) * (dp * 2) == count:
            dp *= 2
        cp = count // dp
    mesh_devices = np.asarray(devices).reshape(dp, cp)
    from jax.sharding import Mesh

    return Mesh(mesh_devices, ("dp", "cp"))


def make_sharded_solve(mesh, num_vars: int):
    """Jitted sharded solve: lits[C,K] sharded over cp rows, assign
    [B,V+1] sharded over dp.

    The DPLL core is ops.batched_sat.build_solve_lane; this wrapper
    only supplies the cross-shard reduce (psum of forced-literal votes,
    conflict flags and decision scores over the clause axis) and the
    shard_map layout.  The psum-merged quantities are identical on
    every clause shard, so all replicas of a lane take the same
    decisions and backtracks — the search stays in lockstep across cp
    with no further synchronization.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from mythril_tpu.ops.batched_sat import build_solve_lane

    def reduce_over_cp(pos, neg, conflict, spos, sneg):
        pos = jax.lax.psum(pos, "cp")
        neg = jax.lax.psum(neg, "cp")
        conflict = jax.lax.psum(conflict.astype(jnp.int32), "cp") > 0
        spos = jax.lax.psum(spos, "cp")
        sneg = jax.lax.psum(sneg, "cp")
        return pos, neg, conflict, spos, sneg

    solve_lane = build_solve_lane(
        num_vars,
        reduce_hook=reduce_over_cp,
        max_steps=MAX_STEPS,
        max_decisions=MAX_DECISIONS,
    )

    def solve_shard(lits_shard, assign_shard):
        # vmap over the local lanes; clause shard shared per device
        return jax.vmap(solve_lane, in_axes=(None, 0))(
            lits_shard, assign_shard
        )

    specs = dict(
        mesh=mesh,
        in_specs=(P("cp", None), P("dp", None)),
        out_specs=(P("dp", None), P("dp")),
    )
    return jax.jit(shard_map(solve_shard, check_vma=False, **specs))


def place_on_mesh(mesh, lits: np.ndarray, assign: np.ndarray):
    """Put the clause rows (sharded over ``cp``) and the lane
    assignments (over ``dp``) straight onto their devices: each device
    receives only its shard, none stages the whole batch."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return (
        jax.device_put(lits, NamedSharding(mesh, P("cp", None))),
        jax.device_put(assign, NamedSharding(mesh, P("dp", None))),
    )


def sharded_frontier_solve(
    mesh, lits: np.ndarray, assign: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Solve a frontier batch on the mesh; pads lanes to the dp size and
    clause rows to the cp size."""
    dp = mesh.shape["dp"]
    cp = mesh.shape["cp"]
    batch = assign.shape[0]
    true_v1 = assign.shape[1]
    # bucket the VAR count (true_v1 - 1) to a power of two: the pool
    # grows on every blast, and a per-dispatch shard_map recompile
    # (tens of seconds) would otherwise dominate the whole mesh path.
    # (Bucketing the column count v+1 itself would round an
    # already-bucketed pool up to double the needed width.)  Shares
    # DevicePool's bucket helper so the production caller — which
    # passes a pool already bucketed by it — always hits this cache.
    from mythril_tpu.ops.batched_sat import DevicePool

    num_vars = DevicePool._bucket(true_v1 - 1)
    v1 = num_vars + 1
    if v1 > true_v1:
        # pad columns as assigned-true: nonexistent vars must never
        # consume DPLL decisions or keep the sweep loop running
        assign = np.concatenate(
            [assign, np.ones((batch, v1 - true_v1), np.int8)], axis=1
        )
    # lanes pad to their power-of-two bucket (then to a multiple of
    # dp): every distinct width would otherwise compile its own solve
    from mythril_tpu.ops.batched_sat import lane_bucket

    pad_lanes = -(-lane_bucket(batch) // dp) * dp - batch
    if pad_lanes:
        # pad lanes fully assigned: an all-open lane would keep the
        # data-dependent DPLL loop (and its per-sweep psum) running a
        # full-pool search after every real lane finished
        assign = np.concatenate(
            [assign, np.ones((pad_lanes, assign.shape[1]), np.int8)]
        )
    pad_rows = (-lits.shape[0]) % cp
    if pad_rows:
        lits = np.concatenate(
            [lits, np.zeros((pad_rows, lits.shape[1]), np.int32)]
        )
    cache_key = (id(mesh), num_vars)
    solve = _solve_cache.get(cache_key)
    if solve is None:
        solve = make_sharded_solve(mesh, num_vars)
        _solve_cache.clear()  # one live shape per mesh is enough
        _solve_cache[cache_key] = solve
    final_assign, status = solve(*place_on_mesh(mesh, lits, assign))
    with obs.wait("mesh.fetch"):
        return (
            np.asarray(final_assign)[:batch, :true_v1],
            np.asarray(status)[:batch],
        )
