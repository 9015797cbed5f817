"""Pallas dense-incidence SAT kernel tests (interpret mode on CPU).

The fused kernel (ops/pallas_prop.py) must agree with the gather-style
JAX path and with the native CDCL ground truth: status 2 only for truly
UNSAT assumption sets, and SAT candidates must verify against the
original terms.  Differential coverage the reference never needed — it
trusted z3 (SURVEY.md §4).
"""

import random
import time

import numpy as np
import pytest

from mythril_tpu.ops.pallas_prop import (
    DenseClausePool, PallasSatBackend, make_dense_solve,
)
from mythril_tpu.smt import UGT, ULT, symbol_factory
from mythril_tpu.smt import terms as T
from mythril_tpu.smt.solver import get_blast_context, reset_blast_context


@pytest.fixture(autouse=True)
def fresh_context(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "force")
    # these tests pin the dense-kernel dispatch plane BELOW the word
    # tier: hold the tier off so the synthetic lanes actually reach it
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    # the dense tier now DECLINES cap-fitting cones in favor of the
    # resident kernel (ops/resident.py) — hold that off too so these
    # lanes exercise the dense kernels they pin (the resident path has
    # its own suite in test_resident_kernel.py)
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_KERNEL", "0")
    reset_blast_context()
    yield
    reset_blast_context()


def _lane_constraints(num_lanes=8):
    lanes = []
    for i in range(num_lanes):
        x = symbol_factory.BitVecSym(f"px{i}", 16)
        if i % 2 == 0:  # SAT: x == 7 + i
            lanes.append([x == 7 + i])
        else:  # UNSAT: x < 5 and x > 10
            lanes.append(
                [
                    ULT(x, symbol_factory.BitVecVal(5, 16)),
                    UGT(x, symbol_factory.BitVecVal(10, 16)),
                ]
            )
    return lanes


def test_dense_pool_shapes():
    ctx = get_blast_context()
    x = symbol_factory.BitVecSym("shape_x", 8)
    ctx.blast_lit((x == 3).raw)
    pool = DenseClausePool()
    pool.refresh(ctx.clauses_py, ctx.solver.num_vars)
    assert pool.C >= len(ctx.clauses_py)
    assert pool.V >= ctx.solver.num_vars + 1
    # every literal accounted for exactly once across P/N (column 0 is
    # the scrap cell for coordinate padding — never a real variable)
    total = float(pool.P[:, 1:].sum() + pool.N[:, 1:].sum())
    assert total == sum(len(c) for c in ctx.clauses_py)


def test_unsat_lanes_conflict_in_kernel():
    ctx = get_blast_context()
    lanes = _lane_constraints(8)
    assumption_sets = [
        [ctx.blast_lit(c.raw) for c in lane] for lane in lanes
    ]
    backend = PallasSatBackend()
    assert backend.available_for(ctx)
    results, assignments = backend.check_assumption_sets(
        ctx, assumption_sets
    )
    for i in range(1, 8, 2):
        assert results[i] is False, f"lane {i} should be sound UNSAT"
    # SAT lanes: undecided (None) at kernel level, model must verify
    from mythril_tpu.ops.batched_sat import _env_from_assignment

    for i in range(0, 8, 2):
        assert results[i] is None
        env = _env_from_assignment(ctx, assignments[i])
        for c in lanes[i]:
            assert T.evaluate(c.raw, env) is True, f"lane {i} model bad"


def test_batch_check_states_uses_pallas(monkeypatch):
    from mythril_tpu.laser.ethereum.state.constraints import Constraints
    from mythril_tpu.ops.batched_sat import batch_check_states
    from mythril_tpu.support.support_args import args

    # the host word-level probe decides the SAT lanes before dispatch;
    # drop the residue/profit gates so the UNSAT lanes still reach the
    # kernel (the adaptive gate would route this tiny residue to CDCL)
    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", True)
    lanes = _lane_constraints(6)
    verdicts = batch_check_states([Constraints(lane) for lane in lanes])
    for i, v in enumerate(verdicts):
        if i % 2 == 0:
            assert v is True, f"lane {i}: expected verified SAT, got {v}"
        else:
            assert v is False, f"lane {i}: expected UNSAT, got {v}"


def test_differential_random_cnf_vs_cdcl():
    """Random 3-CNF instances: kernel UNSAT verdicts must match the
    native CDCL; kernel never calls UNSAT on a satisfiable instance."""
    from mythril_tpu.native import SatSolver

    rng = random.Random(1234)
    truths = []
    kernel_unsats = 0
    for trial in range(12):
        num_vars = rng.randint(4, 10)
        num_clauses = rng.randint(6, 42)
        clauses = []
        for _ in range(num_clauses):
            width = rng.randint(1, 3)
            clause = tuple(
                rng.choice([1, -1]) * rng.randint(2, num_vars + 1)
                for _ in range(width)
            )
            clauses.append(clause)

        ref = SatSolver()
        for _ in range(num_vars + 2):
            ref.new_var()
        ok = True
        for clause in clauses:
            if not ref.add_clause(list(clause)):
                ok = False
                break
        truth = ok and ref.solve([1]) == SatSolver.SAT

        pool = DenseClausePool()
        pool.refresh(clauses, num_vars + 1)
        B = 8
        import jax
        import jax.numpy as jnp

        A0 = np.zeros((B, pool.V), dtype=np.float32)
        A0[:, 1] = 1.0
        A0[:, num_vars + 2:] = 1.0  # bucket padding: preassigned
        step = make_dense_solve(pool.C, pool.V, B, 96, True)
        A, st, _steps = step(
            pool.P, pool.N, pool.width, jnp.asarray(A0),
        )
        status = int(np.asarray(st)[0, 0])
        truths.append(truth)
        if status == 2:
            kernel_unsats += 1
            assert not truth, f"trial {trial}: kernel UNSAT on SAT instance"
        elif status == 1:
            # complete assignment: must satisfy every clause
            assert truth, f"trial {trial}: kernel SAT on UNSAT instance"
            signs = np.sign(np.asarray(A))[0]
            for clause in clauses:
                assert any(
                    signs[abs(l)] == (1 if l > 0 else -1) for l in clause
                ), f"trial {trial}: device model violates {clause}"
        # DPLL with an adequate budget must decide these tiny instances
        assert status in (1, 2), f"trial {trial}: undecided tiny CNF"
    # vacuity guard: the corpus must exercise both outcomes
    assert any(truths) and not all(truths), "corpus not discriminating"
    assert kernel_unsats > 0, "kernel never produced an UNSAT verdict"


def test_dpll_decides_where_bcp_cannot():
    """Instances with no unit clauses (the BCP fixpoint is empty) that
    need genuine decision search.  UNSAT: binary contradiction squares
    chained over several variable pairs — every clause is width ≥ 2, so
    refutation requires deciding, propagating, conflicting,
    backtracking, and exhausting both phases.  SAT: an implication ring
    with no units.  The round-2 kernel (BCP + WalkSAT) returned
    undecided on exactly this shape; the DPLL must decide it."""
    import jax
    import jax.numpy as jnp

    # UNSAT: (a|b)(a|-b)(-a|b)(-a|-b) over pair (2,3), plus a second
    # pair (4,5) constrained satisfiably so the search must navigate
    # non-conflicting structure too
    unsat = [
        (2, 3), (2, -3), (-2, 3), (-2, -3),
        (4, 5), (-4, -5),
    ]
    # SAT: implication ring 2->3->4->5->2 (all width 2, no units)
    sat = [(-2, 3), (-3, 4), (-4, 5), (-5, 2), (2, 4)]

    for clauses, want in ((unsat, 2), (sat, 1)):
        num_vars = 6
        pool = DenseClausePool()
        pool.refresh(clauses, num_vars)
        B = 8
        A0 = np.zeros((B, pool.V), dtype=np.float32)
        A0[:, 1] = 1.0
        A0[:, num_vars + 1:] = 1.0  # bucket padding: preassigned
        step = make_dense_solve(pool.C, pool.V, B, 192, True)
        A, st, _steps = step(
            pool.P, pool.N, pool.width, jnp.asarray(A0),
        )
        status = int(np.asarray(st)[0, 0])
        assert status == want, f"want {want}, got {status}"
        if want == 1:
            signs = np.sign(np.asarray(A))[0]
            for clause in clauses:
                assert any(
                    signs[abs(l)] == (1 if l > 0 else -1) for l in clause
                )


def test_wide_clauses_not_dropped():
    """Clauses wider than the gather path's MAX_CLAUSE_WIDTH are fully
    represented densely: an unsatisfiable wide instance conflicts."""
    import jax
    import jax.numpy as jnp

    num_vars = 16
    wide = tuple(range(2, 14))  # x2 or x3 or ... or x13  (width 12)
    clauses = [wide] + [(-v,) for v in range(2, 14)]
    pool = DenseClausePool()
    pool.refresh(clauses, num_vars)
    B = 8
    A0 = np.zeros((B, pool.V), dtype=np.float32)
    A0[:, 1] = 1.0
    step = make_dense_solve(pool.C, pool.V, B, 4, True)
    _, st, _steps = step(
        pool.P, pool.N, pool.width, jnp.asarray(A0),
    )
    assert int(np.asarray(st)[0, 0]) == 2


def test_pool_not_grafted_across_context_reset(monkeypatch):
    """A process-global backend must rebuild its device pool when the
    blast context is reset: appending the new context's clauses onto the
    old pool at stale offsets would make device UNSAT verdicts unsound
    (feasible paths of the new contract pruned against the old one's
    CNF).  Forces the gather path — the dense Pallas path extracts a
    per-call cone and has no resident pool."""
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    from mythril_tpu.ops.batched_sat import BatchedSatBackend

    backend = BatchedSatBackend()
    ctx_a = get_blast_context()
    lanes_a = _lane_constraints(8)
    sets_a = [[ctx_a.blast_lit(c.raw) for c in lane] for lane in lanes_a]
    backend.check_assumption_sets(ctx_a, sets_a)
    assert backend.pool_generation == ctx_a.generation

    reset_blast_context()
    ctx_b = get_blast_context()
    assert ctx_b.generation != ctx_a.generation
    x = symbol_factory.BitVecSym("graft_x", 16)
    lanes_b = [
        [x == 3],  # SAT — must never be pruned as UNSAT
        [
            ULT(x, symbol_factory.BitVecVal(5, 16)),
            UGT(x, symbol_factory.BitVecVal(10, 16)),
        ],  # BCP-decidable UNSAT
    ]
    sets_b = [[ctx_b.blast_lit(c.raw) for c in lane] for lane in lanes_b]
    results = backend.check_assumption_sets(ctx_b, sets_b)
    assert backend.pool_generation == ctx_b.generation
    assert results[0] is not False, "SAT lane pruned: pool was grafted"
    assert results[1] is False


def test_futile_dispatch_fuse(monkeypatch):
    """Consecutive zero-decision device dispatches trip the fuse: the
    frontier then goes straight to the CDCL tail for the rest of that
    blast context (paying kernel latency for undecided lanes only is
    strictly worse), and a fresh context re-arms it."""
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "word_probing", False)
    monkeypatch.setattr(args, "device_force_dispatch", True)
    # this test pins the FUSE semantics: every call must reach the
    # backend, so the coalescer's admission window stays out of the way
    monkeypatch.setattr(args, "device_coalesce", False)
    backend = BS.get_backend()

    # force "engaged but nothing decided" outcomes without a device:
    # all-None verdicts with an all-zero assignment that cannot verify
    # against the lanes below (x == i+1 is false under x = 0)
    def fake_check(self, ctx, sets, search=True):
        self.device_engaged = True
        self.last_assignments = np.zeros(
            (len(sets), ctx.solver.num_vars + 1), np.int8
        )
        return [None] * len(sets)

    monkeypatch.setattr(
        BS.BatchedSatBackend, "check_assumption_sets", fake_check
    )
    ctx = get_blast_context()
    lanes = []
    for i in range(4):
        x = symbol_factory.BitVecSym(f"fuse_x{i}", 16)
        lanes.append([x == i + 1])
    sets = [[c for c in lane] for lane in lanes]
    before = BS.dispatch_stats.dispatches
    for _ in range(BS.FUTILE_DISPATCH_FUSE):
        BS.batch_check_states(sets)
    assert backend.fused_generation == ctx.generation
    fused_count = BS.dispatch_stats.dispatches
    BS.batch_check_states(sets)  # fused: no further dispatch
    assert BS.dispatch_stats.dispatches == fused_count
    assert fused_count - before == BS.FUTILE_DISPATCH_FUSE
    assert BS.dispatch_stats.fused is True

    reset_blast_context()  # new context re-arms the fuse
    ctx2 = get_blast_context()
    y = symbol_factory.BitVecSym("fuse_y", 16)
    BS.batch_check_states([[y == 1], [y == 2]])
    assert BS.dispatch_stats.dispatches == fused_count + 1
    assert backend.fused_generation != ctx2.generation


@pytest.mark.parametrize("compiling", [True, False])
def test_slow_fuse_nets_out_compile_time(monkeypatch, compiling):
    """One zero-decision dispatch slower than SLOW_DISPATCH_FUSE_S
    fuses the device off for the context — unless the time went to a
    jax compile: a cold kernel compile is set-up, and counting it fused
    a v5e off after its first dispatch, so a warm-cache rerun took a
    different path."""
    import jax.monitoring

    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "word_probing", False)
    monkeypatch.setattr(args, "device_force_dispatch", True)
    monkeypatch.setattr(args, "device_coalesce", False)
    monkeypatch.setattr(BS, "SLOW_DISPATCH_FUSE_S", 0.2)
    backend = BS.get_backend()
    event = "/jax/core/compile/backend_compile_duration"

    def slow_check(self, ctx, sets, search=True):
        if compiling:
            jax.monitoring.record_scalar(event, time.time())
        time.sleep(0.4)
        if compiling:
            jax.monitoring.record_event_duration_secs(event, 0.4)
        self.device_engaged = True
        self.last_assignments = np.zeros(
            (len(sets), ctx.solver.num_vars + 1), np.int8
        )
        return [None] * len(sets)

    monkeypatch.setattr(
        BS.BatchedSatBackend, "check_assumption_sets", slow_check
    )
    reset_blast_context()
    ctx = get_blast_context()
    x = symbol_factory.BitVecSym("slow_fuse_x", 16)
    BS.batch_check_states([[x == 1], [x == 2]])
    assert (backend.fused_generation == ctx.generation) is not compiling
    assert BS.dispatch_stats.fused is not compiling


def test_fuse_retry_rearms_on_decision(monkeypatch):
    """A fused context re-probes the device every FUSE_RETRY_PERIOD
    eligible rounds; a retry that decides lanes re-arms the fuse (the
    workload shape changes as execution advances — SAT-heavy dispatch
    rounds give way to dead-path guard rounds BCP kills in bulk)."""
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "word_probing", False)
    monkeypatch.setattr(args, "device_force_dispatch", True)
    # fuse-retry cadence test: the admission window must not swallow
    # the non-retry calls it counts
    monkeypatch.setattr(args, "device_coalesce", False)
    backend = BS.get_backend()
    mode = {"deciding": False}

    def fake_check(self, ctx, sets, search=True):
        self.device_engaged = True
        self.last_assignments = np.zeros(
            (len(sets), ctx.solver.num_vars + 1), np.int8
        )
        if mode["deciding"]:
            return [False] * len(sets)  # device UNSAT for every lane
        return [None] * len(sets)

    monkeypatch.setattr(
        BS.BatchedSatBackend, "check_assumption_sets", fake_check
    )
    reset_blast_context()
    ctx = get_blast_context()
    x = symbol_factory.BitVecSym("retry_x", 16)
    sets = [[x == 1], [x == 2]]
    for _ in range(BS.FUTILE_DISPATCH_FUSE):
        BS.batch_check_states(sets)
    assert backend.fused_generation == ctx.generation
    fused_count = BS.dispatch_stats.dispatches

    # rounds 1..7 after the fuse stay skipped; the 8th retries
    mode["deciding"] = True
    for i in range(BS.FUSE_RETRY_PERIOD - 1):
        BS.batch_check_states(sets)
        assert BS.dispatch_stats.dispatches == fused_count, f"round {i}"
    verdicts = BS.batch_check_states(sets)  # retry dispatch
    assert BS.dispatch_stats.dispatches == fused_count + 1
    assert verdicts == [False, False]
    assert backend.fused_generation != ctx.generation  # re-armed
    assert BS.dispatch_stats.fused is False


def test_bulk_completion_deep_sat_never_unsat():
    """Planted-satisfiable instances deep enough to leave the single-var
    window (> DPLL_SINGLE_WINDOW constrained decisions) exercise bulk
    levels and their taint bookkeeping: the kernel must never report
    UNSAT for a satisfiable instance, and a completion must satisfy
    every clause."""
    import jax.numpy as jnp

    from mythril_tpu.ops.pallas_prop import DPLL_SINGLE_WINDOW

    rng = random.Random(99)
    for trial in range(4):
        num_vars = 40
        planted = {
            v: rng.choice([True, False]) for v in range(2, num_vars + 2)
        }
        clauses = []
        for _ in range(140):
            picks = rng.sample(sorted(planted), 3)
            lits = [
                v if rng.random() < 0.5 else -v for v in picks
            ]
            # force at least one literal true under the planted model
            w = picks[0]
            lits[0] = w if planted[w] else -w
            clauses.append(tuple(lits))
        pool = DenseClausePool()
        pool.refresh(clauses, num_vars + 2)
        B = 8
        A0 = np.zeros((B, pool.V), dtype=np.float32)
        A0[:, 1] = 1.0
        A0[:, num_vars + 2:] = 1.0
        step = make_dense_solve(pool.C, pool.V, B, 192, True)
        A, st, _ = step(pool.P, pool.N, pool.width, jnp.asarray(A0))
        status = int(np.asarray(st)[0, 0])
        assert status != 2, f"trial {trial}: UNSAT claimed on SAT instance"
        if status == 1:
            signs = np.sign(np.asarray(A))[0]
            for clause in clauses:
                assert any(
                    signs[abs(l)] == (1 if l > 0 else -1) for l in clause
                ), f"trial {trial}: bulk completion violates {clause}"
    assert num_vars > 2 * DPLL_SINGLE_WINDOW  # instances leave the window


def test_unsat_within_single_window_still_refutes():
    """An unsatisfiable core whose refutation fits the single-var window
    must still produce the sound status 2 — the taint machinery may
    only downgrade refutations that crossed a bulk level."""
    import jax.numpy as jnp

    # (a|b)(a|-b)(-a|b)(-a|-b): refuted with one decision + one flip
    clauses = [(2, 3), (2, -3), (-2, 3), (-2, -3)]
    pool = DenseClausePool()
    pool.refresh(clauses, 4)
    B = 8
    A0 = np.zeros((B, pool.V), dtype=np.float32)
    A0[:, 1] = 1.0
    A0[:, 4:] = 1.0
    step = make_dense_solve(pool.C, pool.V, B, 64, True)
    _, st, _ = step(pool.P, pool.N, pool.width, jnp.asarray(A0))
    assert int(np.asarray(st)[0, 0]) == 2


def test_batched_layout_differential_random_cnf():
    """The per-lane batched kernel (make_batched_solve) on independent
    random instances per lane: UNSAT verdicts must match the native
    CDCL, and completions must satisfy their own lane's clauses."""
    import jax.numpy as jnp

    from mythril_tpu.native import SatSolver
    from mythril_tpu.ops.pallas_prop import make_batched_solve

    rng = random.Random(4321)
    B, C, V = 8, 128, 128
    P = np.zeros((B, C, V), np.float32)
    N = np.zeros((B, C, V), np.float32)
    W = np.zeros((B, C), np.float32)
    A0 = np.zeros((B, V), np.float32)
    A0[:, 1] = 1.0
    truths, lane_clauses = [], []
    for lane in range(B):
        num_vars = rng.randint(5, 12)
        clauses = []
        for _ in range(rng.randint(8, 48)):
            width = rng.randint(1, 3)
            clauses.append(tuple(
                rng.choice([1, -1]) * rng.randint(2, num_vars + 1)
                for _ in range(width)
            ))
        lane_clauses.append(clauses)
        ref = SatSolver()
        for _ in range(num_vars + 2):
            ref.new_var()
        ok = all(ref.add_clause(list(c)) for c in clauses)
        truths.append(ok and ref.solve([1]) == SatSolver.SAT)
        for row, clause in enumerate(clauses):
            lits = set(clause)
            if any(-l in lits for l in lits):
                continue  # tautology: inert row (width 0)
            W[lane, row] = len(lits)
            for lit in lits:
                (P if lit > 0 else N)[lane, row, abs(lit)] = 1.0
        A0[lane, num_vars + 2:] = 1.0
    step = make_batched_solve(C, V, B, 96)
    A, st, _ = step(
        jnp.asarray(P, jnp.bfloat16), jnp.asarray(N, jnp.bfloat16),
        jnp.asarray(W), jnp.asarray(A0),
    )
    st = np.asarray(st)[:, 0]
    signs = np.sign(np.asarray(A))
    assert any(truths) and not all(truths), "corpus not discriminating"
    for lane in range(B):
        assert st[lane] in (1, 2), f"lane {lane} undecided"
        if st[lane] == 2:
            assert not truths[lane], f"lane {lane}: UNSAT on SAT instance"
        else:
            assert truths[lane], f"lane {lane}: SAT on UNSAT instance"
            for clause in lane_clauses[lane]:
                assert any(
                    signs[lane, abs(l)] == (1 if l > 0 else -1)
                    for l in clause
                ), f"lane {lane}: model violates {clause}"


def test_layout_chooser_picks_batched_for_disjoint_cones(monkeypatch):
    """Disjoint per-lane cones make the union matrix block-diagonal:
    the dispatch must route through the per-lane batched layout and
    still return sound verdicts."""
    from mythril_tpu.ops import pallas_prop as PP

    ctx = get_blast_context()
    lanes = []
    for i in range(16):
        # 16-bit: the MUL circuits keep the per-lane cones disjoint and
        # search-requiring while staying inside the interpret-tier step
        # budget (3 is odd, so 3x == t is always satisfiable mod 2^16)
        x = symbol_factory.BitVecSym(f"dj{i}", 16)
        if i % 2 == 0:
            lanes.append([x * symbol_factory.BitVecVal(3, 16) == 9 + i])
        else:
            lanes.append([
                ULT(x, symbol_factory.BitVecVal(5, 16)),
                UGT(x, symbol_factory.BitVecVal(10, 16)),
            ])
    sets = [[ctx.blast_lit(c.raw) for c in lane] for lane in lanes]
    routed = {}
    be = PP.PallasSatBackend()
    orig_b, orig_u = be._solve_batched, be._solve_union

    def spy_batched(*a, **k):
        routed.setdefault("layout", "batched")
        return orig_b(*a, **k)

    def spy_union(*a, **k):
        routed.setdefault("layout", "union")
        return orig_u(*a, **k)

    monkeypatch.setattr(be, "_solve_batched", spy_batched)
    monkeypatch.setattr(be, "_solve_union", spy_union)
    out = be.check_assumption_sets(ctx, sets)
    assert out is not None
    results, assignments = out
    assert routed.get("layout") == "batched"
    for i in range(1, 16, 2):
        assert results[i] is False, f"lane {i} should be sound UNSAT"
    from mythril_tpu.ops.batched_sat import _env_from_assignment

    for i in range(0, 16, 2):
        env = _env_from_assignment(ctx, assignments[i])
        for c in lanes[i]:
            assert T.evaluate(c.raw, env) is True, f"lane {i} model bad"


def test_profit_gate_routes_cheap_residues_to_cdcl(monkeypatch):
    """The adaptive dispatch gate: when the analysis's own observed
    native CDCL cost projects the residue cheaper than a device
    dispatch, the frontier goes straight to the CDCL tail and the skip
    is counted (device never pays unless it is projected to win)."""
    from mythril_tpu.laser.ethereum.state.constraints import Constraints
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.smt.solver import SolverStatistics
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "word_probing", False)
    stats = SolverStatistics()
    monkeypatch.setattr(stats, "enabled", True)
    monkeypatch.setattr(stats, "native_s", 0.004)   # observed 2 ms/query
    monkeypatch.setattr(stats, "native_calls", 2)
    lanes = _lane_constraints(6)
    BS.dispatch_stats.reset()
    before = BS.dispatch_stats.dispatches
    BS.batch_check_states([Constraints(lane) for lane in lanes])
    assert BS.dispatch_stats.dispatches == before  # no dispatch paid
    assert BS.dispatch_stats.profit_skips >= 1


def test_cone_gather_tier_on_oversized_pool(monkeypatch):
    """Union-cone gather tier (VERDICT r4 #4/#7): when the pool
    outgrows the full-pool gather caps but the batch's union cone
    fits, the dispatch ships only the cone (subset CSR, compacted
    vars) and still produces sound verdicts: UNSAT lanes refute,
    SAT lanes complete with models that verify on the full terms."""
    import numpy as np

    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.smt import UGT, ULT, symbol_factory
    from mythril_tpu.smt import terms as T
    from mythril_tpu.smt.solver import get_blast_context

    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    ctx = get_blast_context()
    # fatten the pool far past the full-pool caps with foreign gates
    # (64-bit multiplier circuits no query below references)
    for i in range(3):
        w = symbol_factory.BitVecSym(f"cone_fat{i}", 64)
        ctx.blast_lit(
            (w * symbol_factory.BitVecVal(0x6D2B + 2 * i, 64)
             == symbol_factory.BitVecVal(1234 + i, 64)).raw
        )
    assert ctx.pool.num_clauses > BS.MAX_GATHER_CLAUSES
    # small-cone query lanes over a fresh 16-bit var
    lanes = []
    for i in range(6):
        x = symbol_factory.BitVecSym(f"cone_q{i}", 16)
        if i % 2 == 0:
            lanes.append([x == 5 + i])
        else:
            lanes.append(
                [ULT(x, symbol_factory.BitVecVal(2, 16)),
                 UGT(x, symbol_factory.BitVecVal(9, 16))]
            )
    assumption_sets = [
        [ctx.blast_lit(c.raw) for c in lane] for lane in lanes
    ]
    backend = BS.get_backend()
    verdicts = backend.check_cone_gather(ctx, assumption_sets)
    assert verdicts is not None, "union cone should fit the tier"
    assert backend.device_engaged
    for i in range(1, 6, 2):
        assert verdicts[i] is False, f"lane {i} must refute on-device"
    for i in range(0, 6, 2):
        # candidate lane: the expanded full-width assignment must
        # verify against the original terms
        assert verdicts[i] is None
        env = BS._env_from_assignment(ctx, backend.last_assignments[i])
        for c in lanes[i]:
            assert T.evaluate(c.raw, env) is True
