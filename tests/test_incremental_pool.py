"""Unit tests for the incremental dispatch plane: resident clause
pool + delta uploads, parent-model warm starts, cross-dispatch cone
memoization, and the checkpoint-resume invalidation contract.

Marked ``perf``: like the sweep-scheduler tests, these pin the policy
the perf numbers in docs/perf.md depend on (``pytest -m perf``), and
stay tier-1 (fast, CPU-only — the gather kernels run on the jax CPU
backend).
"""

import numpy as np
import pytest

from mythril_tpu.ops import batched_sat as BS
from mythril_tpu.ops.batched_sat import (
    BatchedSatBackend,
    DevicePool,
    dispatch_stats,
    warm_pref_row,
)
from mythril_tpu.ops.incremental import (
    ConeMemo,
    get_cone_memo,
    reset_cone_memo,
    resident_pool_enabled,
    warm_start_enabled,
)
from mythril_tpu.smt import terms as T
from mythril_tpu.smt.bitblast import BlastContext

pytestmark = pytest.mark.perf


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Fresh stats/memo per test; pin the plane's env knobs on so
    ambient MYTHRIL_TPU_* settings can't skew the assertions."""
    for var in ("MYTHRIL_TPU_RESIDENT_POOL", "MYTHRIL_TPU_WARM_START"):
        monkeypatch.delenv(var, raising=False)
    dispatch_stats.reset()
    reset_cone_memo()
    yield
    dispatch_stats.reset()
    reset_cone_memo()


def _ctx_with_clauses(n_eq: int = 4):
    """BlastContext holding a few blasted 8-bit equality constraints;
    returns (ctx, assumption literal list)."""
    ctx = BlastContext()
    lits = []
    for i in range(n_eq):
        x = T.var(f"x{i}", 8)
        lits.append(ctx.blast_lit(T.eq(x, T.const(17 * i + 3, 8))))
    return ctx, lits


# ------------------------------------------------- resident pool


def test_resident_pool_delta_append_matches_full_rebuild():
    """A delta append must leave the host mirror identical to a from-
    scratch rebuild (delta-vs-full upload equivalence), and count as a
    delta, not a full upload."""
    ctx, lits = _ctx_with_clauses(2)
    pool = DevicePool()
    pool.refresh(ctx, ctx.solver.num_vars)
    assert dispatch_stats.pool_uploads == 1
    baseline_filled = pool.filled

    x = T.var("late", 8)
    ctx.blast_lit(T.eq(x, T.const(99, 8)))  # grow the pool
    assert pool.append(ctx, ctx.solver.num_vars) is True
    assert dispatch_stats.delta_uploads == 1
    assert pool.filled > baseline_filled

    fresh = DevicePool()
    fresh.refresh(ctx, ctx.solver.num_vars)
    assert fresh.filled == pool.filled
    np.testing.assert_array_equal(
        fresh.lits_np[: fresh.filled], pool.lits_np[: pool.filled]
    )
    # the resident device copy mirrors the host exactly
    np.testing.assert_array_equal(
        np.asarray(pool.lits)[: pool.filled], pool.lits_np[: pool.filled]
    )


def test_sync_pool_version_and_generation_invalidation():
    """_sync_pool_and_assign: same version = no upload at all; version
    bump = delta append; new blast-context generation = full rebuild."""
    ctx, lits = _ctx_with_clauses(2)
    backend = BatchedSatBackend()
    nv = ctx.solver.num_vars
    backend._sync_pool_and_assign(ctx, [lits], nv)
    assert (dispatch_stats.pool_uploads,
            dispatch_stats.delta_uploads) == (1, 0)

    backend._sync_pool_and_assign(ctx, [lits], nv)  # unchanged pool
    assert (dispatch_stats.pool_uploads,
            dispatch_stats.delta_uploads) == (1, 0)

    ctx.blast_lit(T.eq(T.var("d", 8), T.const(5, 8)))
    backend._sync_pool_and_assign(ctx, [lits], ctx.solver.num_vars)
    assert dispatch_stats.delta_uploads == 1

    ctx2, lits2 = _ctx_with_clauses(2)  # new generation: never grafted
    backend._sync_pool_and_assign(ctx2, [lits2], ctx2.solver.num_vars)
    assert dispatch_stats.pool_uploads == 2
    assert backend.pool_generation == ctx2.generation


def test_resident_pool_kill_switch_forces_full_uploads(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_POOL", "0")
    assert not resident_pool_enabled()
    ctx, lits = _ctx_with_clauses(2)
    backend = BatchedSatBackend()
    nv = ctx.solver.num_vars
    backend._sync_pool_and_assign(ctx, [lits], nv)
    backend._sync_pool_and_assign(ctx, [lits], nv)
    assert dispatch_stats.pool_uploads == 2  # re-uploaded per dispatch
    assert dispatch_stats.delta_uploads == 0


def test_h2d_bytes_steady_state_is_assumptions_only():
    """With the pool resident, a repeat dispatch's payload is just the
    assumption matrix — the >=50%-smaller-h2d acceptance invariant at
    unit scale."""
    ctx, lits = _ctx_with_clauses(3)
    backend = BatchedSatBackend()
    nv = ctx.solver.num_vars
    backend._sync_pool_and_assign(ctx, [lits], nv)
    first = dispatch_stats.h2d_bytes
    dispatch_stats.h2d_bytes = 0
    assign = backend._sync_pool_and_assign(ctx, [lits], nv)
    assert dispatch_stats.h2d_bytes == assign.nbytes
    assert dispatch_stats.h2d_bytes < first / 2


# ------------------------------------------------------ cone memo


def test_cone_memo_hits_and_version_refresh():
    """Same roots + same pool version = a hit returning equal arrays;
    a pool-version move (the repack/invalidation case) drops the table
    and a fresh walk sees the new clauses."""
    ctx, lits = _ctx_with_clauses(3)
    memo = ConeMemo()
    ci1, cv1 = memo.cone(ctx, lits[:2])
    assert dispatch_stats.cone_memo_hits == 0
    ci2, cv2 = memo.cone(ctx, lits[:2])
    assert dispatch_stats.cone_memo_hits == 1
    np.testing.assert_array_equal(ci1, ci2)
    np.testing.assert_array_equal(cv1, cv2)
    direct_ci, direct_cv = ctx.pool.cone(lits[:2])
    np.testing.assert_array_equal(ci2, direct_ci)
    np.testing.assert_array_equal(cv2, direct_cv)

    before = ctx.pool_version
    extra = ctx.blast_lit(T.eq(T.var("g", 8), T.const(7, 8)))
    assert ctx.pool_version != before
    ci3, _cv3 = memo.cone(ctx, lits[:2] + [extra])
    assert dispatch_stats.cone_memo_hits == 1  # scope moved: a miss
    direct_ci3, _ = ctx.pool.cone(lits[:2] + [extra])
    np.testing.assert_array_equal(ci3, direct_ci3)
    assert len(memo) == 1  # the old scope's entries were dropped


def test_cone_memo_caches_declines_and_is_bounded():
    ctx, lits = _ctx_with_clauses(1)
    memo = ConeMemo()
    calls = []
    assert memo.get_or_build(ctx, ("k",), lambda: calls.append(1)) is None
    assert memo.get_or_build(ctx, ("k",), lambda: calls.append(1)) is None
    assert len(calls) == 1  # the decline was cached, not re-walked
    for i in range(200):
        memo.get_or_build(ctx, ("fill", i), lambda: i)
    from mythril_tpu.ops.incremental import CONE_MEMO_CAP

    assert len(memo) <= CONE_MEMO_CAP


def test_build_cone_batch_memoizes_rows_across_dispatches():
    """Sibling dispatches with the same union roots skip the host CSR
    walk: second _build_cone_batch is a memo hit and returns identical
    rows."""
    ctx, lits = _ctx_with_clauses(3)
    backend = BatchedSatBackend()
    sets = [[lit] for lit in lits]
    built1 = backend._build_cone_batch(ctx, sets)
    assert built1 is not None
    hits_after_first = dispatch_stats.cone_memo_hits
    built2 = backend._build_cone_batch(ctx, sets)
    assert dispatch_stats.cone_memo_hits == hits_after_first + 1
    np.testing.assert_array_equal(built1[0], built2[0])
    np.testing.assert_array_equal(built1[2], built2[2])
    assert built1[0] is built2[0]  # the SAME cached array, no rebuild


# ----------------------------------------------------- warm starts


def test_model_channel_tagging_and_warm_phase_vector(monkeypatch):
    """A CDCL SAT verdict tags its model with the literal truth row;
    warm_phase_vector replays it as +-1 phases (anchor forced true)."""
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "word_probing", False)  # force CDCL
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")  # past the word tier
    ctx, lits = _ctx_with_clauses(2)
    x = T.var("x0", 8)
    status, env = ctx.check([T.eq(x, T.const(3, 8))])
    assert status == 1
    assert getattr(env, "truth_snapshot", None) is not None
    warm = ctx.warm_phase_vector(ctx.solver.num_vars)
    assert warm is not None
    assert warm.dtype == np.int8
    assert warm[1] == 1  # constant-TRUE anchor
    assert set(np.unique(warm)) <= {-1, 0, 1}


def test_warm_pref_row_kill_switch_and_remap(monkeypatch):
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "word_probing", False)  # force CDCL
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")  # past the word tier
    ctx, lits = _ctx_with_clauses(1)
    ctx.check([T.eq(T.var("x0", 8), T.const(3, 8))])
    row = warm_pref_row(ctx, ctx.solver.num_vars + 1, lanes=4)
    assert row is not None
    assert dispatch_stats.warm_start_hits == 4
    # compact cone remap: cone_vars[i] -> column i + offset
    cone_vars = np.asarray([2, 3, 5], np.int64)
    compact = warm_pref_row(ctx, 5, cone_vars=cone_vars, offset=1)
    assert compact is not None
    full = ctx.warm_phase_vector(ctx.solver.num_vars)
    assert compact[1] == full[2] and compact[2] == full[3]
    monkeypatch.setenv("MYTHRIL_TPU_WARM_START", "0")
    assert not warm_start_enabled()
    assert warm_pref_row(ctx, ctx.solver.num_vars + 1) is None


def test_warm_start_biases_phase_but_not_verdicts():
    """Kernel-level parity: on the same clause set, warm-started and
    cold lanes reach the same SAT/UNSAT verdicts; the warm lane's
    decision takes the preferred polarity first."""
    import jax.numpy as jnp

    num_vars = 6
    lits = np.zeros((4, BS.MAX_CLAUSE_WIDTH), np.int32)
    lits[0, 0] = 1          # constant-TRUE anchor unit
    lits[1, :2] = (4, 5)    # open clause: vars 4, 5 free
    V1 = num_vars + 1
    D = max(1, min(BS.GATHER_DECISIONS, V1))  # the kernel's stack depth

    def run(pref_value):
        assign = np.zeros((2, V1), np.int8)
        assign[:, 1] = 1
        assign[1, 4] = -1   # lane 1: force the clause toward var 5
        assign[1, 5] = -1   # ...and falsify it -> BCP conflict, UNSAT
        pref = np.full((2, V1), pref_value, np.int8)
        step = BS.make_round_step(num_vars, 64)
        out = step(
            jnp.asarray(lits), jnp.asarray(assign),
            jnp.zeros((2, V1), jnp.int32),
            jnp.zeros((2, D), jnp.int32),
            jnp.zeros((2, D), jnp.int8),
            jnp.zeros((2, D), bool),
            jnp.zeros(2, jnp.int32),
            jnp.zeros(2, jnp.int32),
            jnp.zeros(2, jnp.int32),
            jnp.asarray(pref),
        )
        return np.asarray(out[0]), np.asarray(out[6])

    cold_assign, cold_status = run(0)
    warm_assign, warm_status = run(-1)
    np.testing.assert_array_equal(cold_status, warm_status)
    assert cold_status[0] == 1 and cold_status[1] == 2
    # lane 0 decided var 4: DLIS tie-break picks +1 cold; the warm
    # preference flips the first polarity tried to -1 (and BCP then
    # satisfies the clause through var 5) — bias, same verdict
    assert cold_assign[0, 4] == 1
    assert warm_assign[0, 4] == -1


def test_warm_start_findings_parity_end_to_end(monkeypatch):
    """The scale workload's findings are identical with the plane on
    vs off (the acceptance invariant, at tier-1 size): warm starts and
    the resident pool only move work, never verdicts."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_faults import _analyze  # reuses the chaos harness

    import jax

    real_devices = jax.devices()
    monkeypatch.setattr(jax, "devices",
                        lambda backend=None: list(real_devices[:1]))
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", True)
    monkeypatch.setattr(args, "async_dispatch", False)
    monkeypatch.setattr(args, "word_probing", False)
    monkeypatch.setattr(args, "batch_width", 32)
    monkeypatch.setattr(args, "device_coalesce", False)

    from mythril_tpu.smt.solver import reset_blast_context

    try:
        found_on, row_on = _analyze()
        monkeypatch.setenv("MYTHRIL_TPU_WARM_START", "0")
        monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_POOL", "0")
        found_off, row_off = _analyze()
    finally:
        reset_blast_context()
    assert found_on == found_off
    assert "106" in found_on
    assert row_on["dispatches"] > 0 and row_off["dispatches"] > 0
    # attribution: this workload dispatches through the cone tier, so
    # the plane's footprint is warm-started lanes (CDCL-tail models
    # seed later dispatches) — and the kill switches zero it out
    assert row_on["warm_start_hits"] > 0
    assert row_off["warm_start_hits"] == 0
    assert row_off["delta_uploads"] == 0


# ------------------------------------- learned-clause append path


def test_learned_clauses_append_as_delta_uploads():
    """Device-learned first-UIP clauses (ops/frontier.py harvest)
    bump the pool version and reach the resident device pool as an
    append-only DELTA upload on the next dispatch — never a full
    rebuild — and the appended rows mirror the learned literals."""
    ctx, lits = _ctx_with_clauses(2)
    backend = BatchedSatBackend()
    nv = ctx.solver.num_vars
    backend._sync_pool_and_assign(ctx, [lits], nv)
    assert dispatch_stats.pool_uploads == 1
    filled = backend.pool.filled

    clause = [-lits[0], -lits[1]]
    assert ctx.harvest_device_clauses([clause]) == 1
    assert ctx.device_learned == 1
    backend._sync_pool_and_assign(ctx, [lits], ctx.solver.num_vars)
    assert dispatch_stats.pool_uploads == 1   # no rebuild
    assert dispatch_stats.delta_uploads == 1  # the learned row shipped
    assert backend.pool.filled == filled + 1
    appended = backend.pool.lits_np[filled]
    assert sorted(appended[appended != 0].tolist()) == sorted(clause)


def test_learned_clauses_survive_warm_start_dispatches():
    """A warm-start (unchanged-pool) dispatch after a learned append
    must keep the learned rows resident: repeat syncs ship assumption
    columns only, and the rows stay in both mirrors."""
    ctx, lits = _ctx_with_clauses(2)
    backend = BatchedSatBackend()
    ctx.harvest_device_clauses([[-lits[0], -lits[1]]])
    backend._sync_pool_and_assign(ctx, [lits], ctx.solver.num_vars)
    filled = backend.pool.filled
    dispatch_stats.h2d_bytes = 0
    assign = backend._sync_pool_and_assign(ctx, [lits],
                                           ctx.solver.num_vars)
    assert dispatch_stats.h2d_bytes == assign.nbytes  # assumptions only
    assert backend.pool.filled == filled
    np.testing.assert_array_equal(
        np.asarray(backend.pool.lits)[:filled],
        backend.pool.lits_np[:filled],
    )


def test_learned_rows_survive_reset_resident_pools():
    """Checkpoint-resume invalidation (reset_resident_pools) drops the
    device mirror but NOT the learned clauses: they live in the native
    pool, so the forced full rebuild re-ships them."""
    ctx, lits = _ctx_with_clauses(2)
    backend = BS.get_backend()
    ctx.harvest_device_clauses([[-lits[0], -lits[1]]])
    backend._sync_pool_and_assign(ctx, [lits], ctx.solver.num_vars)
    rows_before = backend.pool.filled
    BS.reset_resident_pools()
    assert backend.pool_generation == -1
    backend._sync_pool_and_assign(ctx, [lits], ctx.solver.num_vars)
    assert backend.pool.filled == rows_before  # learned row still aboard
    assert dispatch_stats.pool_uploads >= 2    # via a full rebuild


def test_frontier_kill_switch_preserves_learned_rows(monkeypatch):
    """MYTHRIL_TPU_FRONTIER=0 switches the round kernel, not the
    clause store: already-harvested clauses stay in the pool and keep
    shipping with rebuilds."""
    monkeypatch.setenv("MYTHRIL_TPU_FRONTIER", "0")
    ctx, lits = _ctx_with_clauses(2)
    backend = BatchedSatBackend()
    ctx.harvest_device_clauses([[-lits[0], -lits[1]]])
    backend._sync_pool_and_assign(ctx, [lits], ctx.solver.num_vars)
    mat = backend.pool.lits_np[: backend.pool.filled]
    assert any(
        sorted(row[row != 0].tolist()) == sorted([-lits[0], -lits[1]])
        for row in mat
    )


def test_cone_memo_scopes_on_learned_generation():
    """A device-learned harvest must invalidate memoized cone layouts:
    the scope key carries the learned-clause generation explicitly."""
    ctx, lits = _ctx_with_clauses(2)
    memo = ConeMemo()
    memo.cone(ctx, lits[:1])
    memo.cone(ctx, lits[:1])
    assert dispatch_stats.cone_memo_hits == 1
    assert ctx.harvest_device_clauses([[-lits[0], -lits[1]]]) == 1
    memo.cone(ctx, lits[:1])  # scope moved: a miss, fresh walk
    assert dispatch_stats.cone_memo_hits == 1


def test_harvest_rejected_under_proof_log(monkeypatch):
    """An in-kernel resolution is not replayable by the proof checker:
    --proof-log runs harvest nothing (same rule as uncertified
    nogoods)."""
    from mythril_tpu.support.support_args import args

    ctx, lits = _ctx_with_clauses(1)
    monkeypatch.setattr(args, "proof_log", True)
    assert ctx.harvest_device_clauses([[-lits[0]]]) == 0
    assert ctx.device_learned == 0


# ------------------------------------------- checkpoint interplay


def test_checkpoint_resume_invalidates_resident_pool(tmp_path):
    """A resumed process must never serve a pre-resume pool or cone
    memo: literal numbering does not survive the journal."""
    from mythril_tpu.resilience.checkpoint import CheckpointPlane
    from mythril_tpu.smt.solver import get_blast_context, reset_blast_context

    reset_blast_context()
    ctx = get_blast_context()
    ctx.blast_lit(T.eq(T.var("c", 8), T.const(1, 8)))

    class _Laser:
        transaction_count = 1
        open_states = []

    plane = CheckpointPlane()
    plane.configure(str(tmp_path))
    plane.transaction_boundary(_Laser(), 0xAFFE, 0)

    backend = BS.get_backend()
    backend.pool.version = 7
    backend.pool_generation = ctx.generation
    get_cone_memo().get_or_build(ctx, ("stale",), lambda: 1)
    assert len(get_cone_memo()) == 1

    resumed = CheckpointPlane()
    resumed.configure(str(tmp_path), resume=True)
    laser = _Laser()
    assert resumed.restore_transactions(laser, 0xAFFE) == 0
    assert backend.pool_generation == -1
    assert backend.pool.version == -1
    assert len(get_cone_memo()) == 0
    reset_blast_context()


def test_reset_resident_pools_direct():
    ctx, lits = _ctx_with_clauses(1)
    backend = BS.get_backend()
    backend._sync_pool_and_assign(ctx, [lits], ctx.solver.num_vars)
    assert backend.pool_generation == ctx.generation
    BS.reset_resident_pools()
    assert backend.pool_generation == -1
    assert backend.pool.version == -1


# ------------------------------------ compile-cache / warmup contract


def test_no_new_compiles_after_warmup_same_bucket(monkeypatch):
    """Two dispatches of the same bucket shape share every jitted
    round: after the first (warmup) ladder run, the second triggers
    zero new kernel builds (the satellite contract behind the
    persistent-compilation-cache wiring in bench.py/tox.ini)."""
    import jax.numpy as jnp

    ctx, lits = _ctx_with_clauses(2)
    backend = BatchedSatBackend()
    nv = ctx.solver.num_vars
    assign = backend._sync_pool_and_assign(ctx, [lits, lits[:1]], nv)

    builds = []
    orig = BS.make_round_step

    def counting(num_vars, budget):
        builds.append((num_vars, budget))
        return orig(num_vars, budget)

    monkeypatch.setattr(BS, "make_round_step", counting)
    backend._step_cache.clear()
    backend._solve_gather_ladder("gather", backend.pool.lits, assign)
    warm = len(builds)
    assert warm >= 1
    backend._solve_gather_ladder("gather", backend.pool.lits, assign)
    assert len(builds) == warm, "second same-shape dispatch recompiled"


def test_compile_cache_dir_is_placed_from_outside(monkeypatch):
    """One place decides the persistent compile cache: the operator's
    JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache."""
    import os

    from mythril_tpu.ops import compile_cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache_dir() == os.path.join(repo, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/opcache")
    assert compile_cache_dir() == "/tmp/opcache"


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
