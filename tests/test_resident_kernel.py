"""Resident solver tests (ops/resident.py): verdict parity with the
multi-dispatch ladders against a brute-force oracle, mid-dispatch
learned-row sharing through the in-kernel extra pool, the device-side
budget/watchdog exit paths, the ``MYTHRIL_TPU_RESIDENT_KERNEL=0`` kill
switch both ways, the drain + checkpoint-resume seams, and ledger lane
conservation through the real funnel.

Marked ``perf``: tier-1, CPU-only — the persistent kernel runs on the
jax CPU backend exactly like the frontier rounds it subsumes.
"""

import itertools
import os

import numpy as np
import pytest

from mythril_tpu.ops import batched_sat as BS
from mythril_tpu.ops import resident as RK
from mythril_tpu.ops.batched_sat import BatchedSatBackend, dispatch_stats
from mythril_tpu.ops.frontier import FRONTIER_BUDGET_MULT, build_adjacency

pytestmark = pytest.mark.perf

K = BS.MAX_CLAUSE_WIDTH


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Fresh stats per test; pin the knob families so ambient
    MYTHRIL_TPU_* settings can't skew kernel shapes or assertions."""
    for var in ("MYTHRIL_TPU_RESIDENT_KERNEL",
                "MYTHRIL_TPU_RESIDENT_BUDGET",
                "MYTHRIL_TPU_RESIDENT_WATCHDOG",
                "MYTHRIL_TPU_RESIDENT_EXTRA",
                "MYTHRIL_TPU_FRONTIER", "MYTHRIL_TPU_FRONTIER_PERIOD",
                "MYTHRIL_TPU_FRONTIER_FAN", "MYTHRIL_TPU_FRONTIER_DEG"):
        monkeypatch.delenv(var, raising=False)
    dispatch_stats.reset()
    yield
    dispatch_stats.reset()


class _HarvestCtx:
    """Minimal blast-context stand-in: collects harvested clauses."""

    device_learned = 0
    device_learned_generation = 0

    def __init__(self):
        self.harvested = []

    def harvest_device_clauses(self, clauses):
        self.harvested.extend(tuple(sorted(int(x) for x in c))
                              for c in clauses)
        return len(clauses)


def _rows(clauses):
    rows = np.zeros((len(clauses), K), np.int32)
    for i, cl in enumerate(clauses):
        rows[i, : len(cl)] = cl
    return rows


def _brute_sat(clauses, nv, fixed=()):
    """Brute-force SAT over vars 2..nv with var 1 pinned true."""
    for bits in itertools.product([1, -1], repeat=nv - 1):
        asg = {1: 1}
        for i, b in enumerate(bits):
            asg[i + 2] = b
        if not all(asg[abs(l)] * (1 if l > 0 else -1) > 0 for l in fixed):
            continue
        if all(
            any(asg[abs(l)] * (1 if l > 0 else -1) > 0 for l in cl)
            for cl in clauses
        ):
            return True
    return False


def _brute_implied(clauses, nv, clause):
    """formula ⊨ clause iff no model of the formula falsifies it."""
    for bits in itertools.product([1, -1], repeat=nv - 1):
        asg = {1: 1}
        for i, b in enumerate(bits):
            asg[i + 2] = b
        if not all(
            any(asg[abs(l)] * (1 if l > 0 else -1) > 0 for l in cl)
            for cl in clauses
        ):
            continue
        if not any(
            asg[abs(l)] * (1 if l > 0 else -1) > 0 for l in clause
        ):
            return False
    return True


def _solve(backend, rows, assign, ctx=None, pref=None):
    """Run the (resident or multi-dispatch) ladder over dense rows."""
    import jax.numpy as jnp

    ctx = ctx or _HarvestCtx()
    adj = build_adjacency(rows, assign.shape[1])
    frontier = {"adj": jnp.asarray(adj), "ctx": ctx, "col_to_var": None}
    st, fa = backend._solve_gather_ladder(
        "gather", jnp.asarray(rows), assign, pref=pref, frontier=frontier
    )
    return st, fa, ctx


def _run_kernel(clauses, assign, pref_row=None, extra_rows=None,
                max_decisions=32):
    """Direct resident-kernel invocation (no supervisor): returns the
    full output state dict over RESIDENT_STATE_FIELDS."""
    import jax.numpy as jnp

    rows = _rows(clauses)
    B, V1 = assign.shape
    adj = build_adjacency(rows, V1)
    state = RK.resident_state0(assign, B, max_decisions, width=K,
                               pref_row=pref_row)
    if extra_rows is not None:
        for j, cl in enumerate(extra_rows):
            state["extra"][j, : len(cl)] = cl
        state["nextra"][0] = len(extra_rows)
    fn = RK.make_resident_step(V1 - 1, max_decisions)
    out = fn(jnp.asarray(rows), jnp.asarray(adj),
             *[jnp.asarray(state[k]) for k in RK.RESIDENT_STATE_FIELDS])
    return {k: np.asarray(v)
            for k, v in zip(RK.RESIDENT_STATE_FIELDS, out)}


def _random_instance(rng, nv, n_clauses):
    clauses = [[1]]
    for _ in range(n_clauses):
        w = int(rng.integers(1, 4))
        vs = rng.choice(np.arange(2, nv + 1), size=min(w, nv - 1),
                        replace=False)
        clauses.append([int(v) * int(rng.choice([1, -1])) for v in vs])
    return clauses


# ------------------------------------------- state-layout contract


def test_lane_fields_are_the_frontier_layout():
    """Satellite (last PR-8 remainder): BOTH ladders enter the
    resident kernel through the frontier state layout, so retry/bisect
    lane slicing along axis 0 stays valid for every per-lane field."""
    from mythril_tpu.ops.frontier import FRONTIER_STATE_FIELDS

    assert RK.RESIDENT_LANE_FIELDS == FRONTIER_STATE_FIELDS
    assert set(RK.RESIDENT_SHARED_FIELDS) == {
        "extra", "nextra", "stall", "itc"
    }
    for key in ("status", "fullsw", "fsteps", "nlearn", "learned"):
        assert key in RK.RESIDENT_LANE_FIELDS


# ------------------------------- verdict parity / kill switch both ways


def test_resident_matches_kill_switch_ladder_on_random_cnfs(monkeypatch):
    """On random CNFs the resident kernel reaches the same per-lane
    verdicts as the multi-dispatch frontier ladder it replaces, UNSAT
    agrees with the brute-force oracle, and SAT models satisfy the
    clause set — the findings-parity acceptance pin at unit scale,
    exercised through the real ladder entry both ways."""
    rng = np.random.default_rng(31)
    backend = BatchedSatBackend()
    for trial in range(4):
        nv = 8
        clauses = _random_instance(rng, nv, int(rng.integers(10, 22)))
        rows = _rows(clauses)
        V1 = nv + 1
        assign = np.zeros((3, V1), np.int8)
        assign[:, 1] = 1
        assign[1, 2] = 1
        assign[2, 2] = -1

        assert RK.resident_kernel_enabled()
        st_res, fa_res, _ = _solve(backend, rows, assign)
        assert dispatch_stats.resident_dispatches > 0

        monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_KERNEL", "0")
        assert not RK.resident_kernel_enabled()
        before = dispatch_stats.resident_dispatches
        st_lad, _, _ = _solve(backend, rows, assign)
        assert dispatch_stats.resident_dispatches == before
        monkeypatch.delenv("MYTHRIL_TPU_RESIDENT_KERNEL")

        np.testing.assert_array_equal(st_res, st_lad)
        for lane, fixed in enumerate(([1], [1, 2], [1, -2])):
            sat = _brute_sat(clauses, nv, fixed)
            if st_res[lane] == 2:
                assert not sat, (trial, lane)
            if st_res[lane] == 1:
                asg = fa_res[lane]
                assert all(
                    any(asg[abs(l)] * (1 if l > 0 else -1) > 0
                        for l in cl)
                    for cl in clauses
                ), (trial, lane)


def test_resident_collapses_ladder_to_one_dispatch():
    """THE perf pin: a straggler chain long enough to force the
    multi-dispatch ladder through several budget rungs completes in
    exactly ONE device dispatch under the resident kernel — the
    dispatches_per_analysis direction the bench gate holds."""
    n = 64 * FRONTIER_BUDGET_MULT + 60
    clauses = [[1], [2]]
    clauses += [[-(v), v + 1] for v in range(2, n + 2)]
    rows = _rows(clauses)
    V1 = n + 3
    assign = np.zeros((1, V1), np.int8)
    assign[:, 1] = 1
    backend = BatchedSatBackend()

    st, fa, _ = _solve(backend, rows, assign)
    assert st[0] == 1
    assert all(fa[0, 2:n + 3] == 1)  # the whole chain propagated
    assert dispatch_stats.device_dispatch_calls == 1
    assert dispatch_stats.resident_dispatches == 1
    assert dispatch_stats.resident_exit_all_decided == 1
    resident_calls = dispatch_stats.device_dispatch_calls

    dispatch_stats.reset()
    os.environ["MYTHRIL_TPU_RESIDENT_KERNEL"] = "0"
    try:
        st_lad, _, _ = _solve(backend, rows, assign)
    finally:
        del os.environ["MYTHRIL_TPU_RESIDENT_KERNEL"]
    assert st_lad[0] == 1
    # the chain outruns round 1's budget: the ladder needs multiple
    # dispatches where the resident kernel needed one
    assert dispatch_stats.device_dispatch_calls > resident_calls


# ------------------------------------- mid-dispatch learned-row pool


def test_extra_pool_rows_are_visible_to_every_lane():
    """A row seeded in the shared extra pool (not in the clause pool,
    not in the adjacency index) must still constrain every lane: the
    full/gather scans read the extra block uniformly — the property
    that makes a clause one lane learns prune its siblings in the SAME
    dispatch."""
    clauses = [[1], [3, 4]]
    B, V1 = 4, 6
    assign = np.zeros((B, V1), np.int8)
    assign[:, 1] = 1
    out = _run_kernel(clauses, assign, extra_rows=[[-2]])
    assert (out["status"] == 1).all()
    # the extra unit forced var 2 negative in every lane, with the
    # reason naming the extra row (pool row count C=2 -> row id 2)
    assert (out["assign"][:, 2] == -1).all()
    assert (out["reason"][:, 2] == len(clauses)).all()


def test_mid_dispatch_learning_appends_deduped_shared_rows():
    """The textbook first-UIP fixture across sibling lanes: every lane
    walks into the same conflict and learns (¬x) — the shared pool
    must hold exactly ONE copy (append dedup across pool + batch), the
    row must be implied by the formula, and every lane must complete
    SAT after the backtrack."""
    clauses = [[1], [-2, 3], [-3, 4], [-3, -4], [2, 5], [2, 6]]
    nv = 6
    B, V1 = 4, nv + 1
    assign = np.zeros((B, V1), np.int8)
    assign[:, 1] = 1
    pref = np.zeros(V1, np.int8)
    pref[2] = 1  # decide b=+1 first: the conflict branch, every lane
    out = _run_kernel(clauses, assign, pref_row=pref)
    assert (out["status"] == 1).all()
    assert int(out["nextra"][0]) == 1  # deduped: one clause, one row
    learned = [int(x) for x in out["extra"][0] if x != 0]
    assert learned == [-3]
    assert _brute_implied(clauses, nv, learned)


def test_shared_pool_rows_stay_implied_on_random_instances():
    """Soundness of the mid-dispatch pool: every row appended during a
    dispatch over conflict-heavy random instances is implied by the
    FORMULA (never weakened to one lane's assumption cube) — the
    argument that makes sibling visibility and the host harvest
    sound."""
    rng = np.random.default_rng(57)
    for _ in range(3):
        nv = 8
        clauses = _random_instance(rng, nv, 20)
        B, V1 = 4, nv + 1
        assign = np.zeros((B, V1), np.int8)
        assign[:, 1] = 1
        for lane in range(1, B):
            assign[lane, 2 + (lane - 1) % (nv - 1)] = (
                1 if lane % 2 else -1
            )
        out = _run_kernel(clauses, assign)
        for j in range(int(out["nextra"][0])):
            cl = [int(x) for x in out["extra"][j] if x != 0]
            assert cl and _brute_implied(clauses, nv, cl), cl


# --------------------------------------- device-side exit taxonomy


def test_budget_exit_retires_survivors_undecided(monkeypatch):
    """MYTHRIL_TPU_RESIDENT_BUDGET pins the in-kernel iteration count:
    a 1-iteration budget cannot decide a multi-var instance, the
    kernel exits on the budget condition, and the supervisor maps the
    survivors to undecided (CDCL tail) with the exit recorded."""
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_BUDGET", "1")
    clauses = [[1], [2, 3], [-2, 4], [3, 5], [-4, -5, 6]]
    rows = _rows(clauses)
    assign = np.zeros((2, 7), np.int8)
    assign[:, 1] = 1
    backend = BatchedSatBackend()
    st, _, _ = _solve(backend, rows, assign)
    assert (st == 0).all()  # undecided, never a fabricated verdict
    assert dispatch_stats.resident_exit_budget == 1
    assert dispatch_stats.resident_exit_all_decided == 0


def test_watchdog_exit_trips_on_stalled_iterations(monkeypatch):
    """The device-side stall watchdog: with fan=1 a full sweep floods
    the queue with forced units whose gathers force nothing further —
    consecutive no-progress iterations trip the in-kernel counter and
    the kernel exits with live lanes for the host to retire."""
    monkeypatch.setenv("MYTHRIL_TPU_FRONTIER_FAN", "1")
    monkeypatch.setenv("MYTHRIL_TPU_FRONTIER_PERIOD", "32")
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_WATCHDOG", "1")
    nv = 20
    clauses = [[v] for v in range(1, 11)]  # 10 units flood the queue
    assign = np.zeros((1, nv + 1), np.int8)
    assign[:, 1] = 1
    out = _run_kernel(clauses, assign)
    reason = RK.exit_reason(
        out["status"], int(out["stall"][0]), int(out["itc"][0]),
        RK.resident_watchdog_limit(), RK.resident_budget(),
    )
    assert reason == "watchdog"
    assert (out["status"] == 0).all()  # live lanes handed back, sound


def test_all_decided_is_the_healthy_exit():
    """On a fully decidable instance the loop exits because no live
    lane remains — before the budget, without a stall."""
    clauses = [[1], [2], [-2, 3]]
    assign = np.zeros((2, 4), np.int8)
    assign[:, 1] = 1
    out = _run_kernel(clauses, assign)
    reason = RK.exit_reason(
        out["status"], int(out["stall"][0]), int(out["itc"][0]),
        RK.resident_watchdog_limit(), RK.resident_budget(),
    )
    assert reason == "all_decided"
    assert (out["status"] == 1).all()
    assert int(out["itc"][0]) < RK.resident_budget()


# ------------------------------------------- drain / resume seams


def test_drain_returns_every_lane_undecided():
    """A drain requested before launch is honored at the dispatch
    boundary: no kernel runs and every lane retires undecided so the
    analysis can land its final checkpoint."""
    from mythril_tpu.resilience import checkpoint as cp

    rows = _rows([[1], [2, 3]])
    assign = np.zeros((2, 4), np.int8)
    assign[:, 1] = 1
    backend = BatchedSatBackend()
    cp.request_drain("test")
    try:
        st, fa, _ = _solve(backend, rows, assign)
    finally:
        cp.reset_for_tests()
    assert (st == 0).all()
    np.testing.assert_array_equal(fa, assign)  # seed untouched
    assert dispatch_stats.resident_dispatches == 0


def test_resume_invalidation_keeps_the_resident_path_sound():
    """The checkpoint plane's reset_resident_pools() (called on
    resume) drops every cross-dispatch device structure; the resident
    kernel carries NO state between dispatches — the shared extra
    pool / counters are re-seeded zeros each launch — so a solve right
    after invalidation must produce identical verdicts."""
    from mythril_tpu.ops.batched_sat import reset_resident_pools

    rows = _rows([[1], [-2, 3], [2, 3], [-3, -2]])
    assign = np.zeros((2, 4), np.int8)
    assign[:, 1] = 1
    assign[1, 2] = 1
    backend = BatchedSatBackend()
    st_a, fa_a, _ = _solve(backend, rows, assign)
    reset_resident_pools()
    st_b, fa_b, _ = _solve(backend, rows, assign)
    np.testing.assert_array_equal(st_a, st_b)
    np.testing.assert_array_equal(fa_a, fa_b)
    assert dispatch_stats.resident_dispatches == 2


# --------------------------------------- escalation ladder / chaos


def test_retry_rung_absorbs_injected_fault_under_resident():
    """An injected frontier fault raises inside the supervised
    resident dispatch: the retry rung absorbs it and the verdicts are
    identical to the fault-free run — the chaos invariant preserved on
    the single-dispatch shape."""
    from mythril_tpu.resilience import faults, watchdog
    from mythril_tpu.resilience.telemetry import resilience_stats

    faults.reset_for_tests()
    watchdog.reset_for_tests()
    rows = _rows([[1], [-2, 3], [2, 3]])
    assign = np.zeros((2, 4), np.int8)
    assign[:, 1] = 1
    backend = BatchedSatBackend()
    st_clean, _, _ = _solve(backend, rows, assign)
    faults.get_fault_plane().arm("frontier_stall", times=1)
    retries_before = resilience_stats.dispatch_retries
    st_fault, _, _ = _solve(backend, rows, assign)
    np.testing.assert_array_equal(st_clean, st_fault)
    assert resilience_stats.dispatch_retries > retries_before
    assert faults.get_fault_plane().fired.get("frontier_stall") == 1
    faults.reset_for_tests()
    watchdog.reset_for_tests()


# ----------------------------------------- ledger lane conservation


def test_funnel_conserves_lanes_under_resident(monkeypatch):
    """Lane conservation through the real funnel with the resident
    kernel engaged: every opened lane terminates in exactly one tier,
    and the resident dispatch actually carried the device share."""
    import jax

    from mythril_tpu.laser.ethereum.state.constraints import Constraints
    from mythril_tpu.observability import ledger
    from mythril_tpu.ops.async_dispatch import get_async_dispatcher
    from mythril_tpu.ops.batched_sat import batch_check_states
    from mythril_tpu.smt import UGT, ULT, symbol_factory
    from mythril_tpu.smt.solver import reset_blast_context
    from mythril_tpu.support.support_args import args

    # conftest forces 8 virtual XLA devices, which routes the funnel
    # through the sharded-mesh tier; pin one device so the dispatch
    # takes the single-chip ladder the resident kernel lives on
    real_devices = jax.devices()
    monkeypatch.setattr(jax, "devices",
                        lambda backend=None: list(real_devices[:1]))
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", True)
    monkeypatch.setattr(args, "async_dispatch", False)
    monkeypatch.setattr(args, "device_coalesce", False)
    ledger.reset_for_tests()
    reset_blast_context()
    get_async_dispatcher().drop()
    try:
        lanes = []
        for i in range(6):
            x = symbol_factory.BitVecSym(f"res{i}", 16)
            if i % 2 == 0:
                lanes.append([x == 3 + i])
            else:  # UNSAT: x < 2 and x > 9
                lanes.append(
                    [ULT(x, symbol_factory.BitVecVal(2, 16)),
                     UGT(x, symbol_factory.BitVecVal(9, 16))]
                )
        verdicts = batch_check_states(
            [Constraints(lane) for lane in lanes]
        )
        assert len(verdicts) == 6
        snap = ledger.get_ledger().snapshot()
        assert snap["lanes_total"] == 6
        assert sum(snap["decided"].values()) == 6  # conservation
        assert dispatch_stats.resident_dispatches > 0
    finally:
        get_async_dispatcher().drop()
        reset_blast_context()
        ledger.reset_for_tests()


def test_resident_iterations_sum_each_dispatch_itc(monkeypatch):
    """``dispatch_stats.resident_iterations`` is the sum of the ``itc``
    each resident dispatch returns, the analysis record carries the
    same sum, and the count restarts with ``dispatch_stats``."""
    from mythril_tpu.observability import spans

    seen = []
    exit_reason = RK.exit_reason

    def spy(status, stall, itc, watchdog, budget):
        seen.append(itc)
        return exit_reason(status, stall, itc, watchdog, budget)

    monkeypatch.setattr(RK, "exit_reason", spy)
    backend = BatchedSatBackend()
    with spans.analysis("cli.analyze"):
        for clauses in ([[1], [2, 3], [-2, 4]],
                        [[1], [-2, 3], [2, 3], [-3, -2]]):
            assign = np.zeros((2, 5), np.int8)
            assign[:, 1] = 1
            _solve(backend, _rows(clauses), assign)
    assert len(seen) == 2 and min(seen) > 0
    assert dispatch_stats.resident_iterations == sum(seen)
    assert spans.analysis_profiles()[-1]["resident_iterations"] == sum(seen)
    dispatch_stats.reset()
    assert dispatch_stats.resident_iterations == 0


# ------------------------------- the full sweep as count matmuls


def _sweep_case(seed):
    """Random clause rows for one sweep: zero padding rows, widths 1-8
    with literals in random slots, duplicate literals, ``v ∨ ¬v`` rows,
    the TRUE anchor (var 1), and lanes from nearly complete (units,
    conflicts) to nearly empty.  Row 0 is made a unit in lane 1 and a
    conflict in lane 2; V1 past 128 takes a second var-id digit."""
    rng = np.random.default_rng(seed)
    V1 = int(rng.choice([9, 40, 130, 300]))
    C, B = 96, 6
    rows = np.zeros((C, K), np.int32)
    for r in range(C - 16):  # the last 16 rows stay padding
        width = int(rng.integers(1, K + 1))
        lits = (rng.integers(1, V1, size=width)
                * rng.choice([1, -1], size=width))
        if width > 1 and rng.random() < 0.2:
            lits[1] = lits[0]                # duplicate literal
        if width > 1 and rng.random() < 0.2:
            lits[-1] = -lits[0]              # tautology
        if r == 0:
            lits = np.array([2, -3, 4][: min(3, V1 - 2)])
        rows[r, rng.choice(K, size=len(lits), replace=False)] = lits
    rng.shuffle(rows[1:])
    assign = np.zeros((B, V1), np.int8)
    for lane in range(B):
        known = rng.random(V1) < (0.95 - 0.15 * lane)
        assign[lane] = np.where(known, rng.choice([1, -1], size=V1), 0)
    assign[:, 1] = 1                         # the TRUE anchor
    row0 = rows[0][rows[0] != 0]
    assign[1:3, np.abs(row0)] = -np.sign(row0)   # every literal false
    assign[1, abs(row0[0])] = 0                  # ... but one: a unit
    return rows, assign, V1


def _gather_count_scan(V1):
    """The reference full sweep: ``make_scan_rows`` over every row."""
    import jax.numpy as jnp

    from mythril_tpu.ops.frontier import make_scan_rows

    scan_rows = make_scan_rows(V1)

    def count_scan(lits, assign):
        B = assign.shape[0]
        C, width = lits.shape
        return scan_rows(
            jnp.broadcast_to(lits[None], (B, C, width)),
            jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None], (B, C)),
            jnp.ones((B, C), bool), assign, True)

    return (lambda lits: lits), count_scan


@pytest.mark.parametrize("seed", range(8))
def test_count_sweep_matches_the_gather_scan(seed):
    """The resident kernel's full sweep (occurrence-count matmuls over
    the cone) returns all eight outputs of ``make_scan_rows`` with
    ``scores=True`` over every row, value for value and dtype for
    dtype."""
    import jax
    import jax.numpy as jnp

    rows, assign, V1 = _sweep_case(seed)
    sweeps = []
    for count_lits, count_scan in (RK.make_count_scan(V1),
                                   _gather_count_scan(V1)):
        sweep = jax.jit(lambda lits, a: count_scan(count_lits(lits), a))
        sweeps.append(sweep(jnp.asarray(rows), jnp.asarray(assign)))
    got, want = sweeps
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    fpos, fneg, _, _, conflict, _, spos, sneg = map(np.asarray, want)
    assert conflict[2]
    assert (fpos[1] | fneg[1]).any() and (spos + sneg).any()


def _guard_cone():
    """The cone of one ``tree16`` guard, blasted: ``(x & 0xffff) +
    addend & 0xffff == target`` on a 256-bit calldata word."""
    from mythril_tpu.smt import terms as T
    from mythril_tpu.smt.bitblast import BlastContext

    x = T.var("tree16_guard_x", 256)
    mask = T.const(0xFFFF, 256)
    guard = T.eq(T.band(T.add(T.band(x, mask), T.const(0x3A7C, 256)),
                        mask), T.const(0x91E5, 256))
    ctx = BlastContext()
    root = ctx.blast_lit(guard)
    clause_ids, _ = ctx.cone([root])
    clauses = [list(ctx.clauses_py[i]) for i in clause_ids]
    clauses = [c for c in clauses if len(c) <= K] + [[1], [root]]
    used = sorted({abs(l) for c in clauses for l in c} - {1})
    dense = {1: 1, **{v: i + 2 for i, v in enumerate(used)}}
    clauses = [[dense[abs(l)] * (1 if l > 0 else -1) for l in c]
               for c in clauses]
    return clauses, len(used) + 2


def _kernel_case(case):
    """(clauses, assign) for the kernel-parity cases: random CNFs under
    assumption lanes, or the guard's cone with lanes that pin some of
    the word's low bits either way."""
    rng = np.random.default_rng(300 if case == "guard" else 301 + case)
    if case == "guard":
        clauses, V1 = _guard_cone()
        assign = np.zeros((6, V1), np.int8)
        for lane in range(1, 6):
            pinned = rng.choice(np.arange(2, V1), size=3, replace=False)
            assign[lane, pinned] = rng.choice([1, -1], size=3)
    else:
        nv = 10
        clauses = _random_instance(rng, nv, int(rng.integers(18, 40)))
        V1 = nv + 1
        assign = np.zeros((4, V1), np.int8)
        for lane in range(1, 4):
            assign[lane, 2 + lane] = 1 if lane % 2 else -1
    assign[:, 1] = 1
    return clauses, assign


@pytest.mark.parametrize("case", [0, 1, 2, 3, "guard"])
def test_kernel_matches_the_gather_sweep_kernel(case, monkeypatch):
    """One resident dispatch with the count-matmul full sweep leaves
    the whole state — status, trail, reasons, learned rows, the extra
    pool, sweep counts and the iteration count — equal to the same
    kernel built with the gather sweep of ``make_scan_rows``."""
    clauses, assign = _kernel_case(case)
    got = _run_kernel(clauses, assign)
    monkeypatch.setattr(RK, "make_count_scan", _gather_count_scan)
    want = _run_kernel(clauses, assign)
    for key in RK.RESIDENT_STATE_FIELDS:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert int(got["itc"][0]) > 0 and got["fullsw"].any()


# ------------------------------------------------- env knob surface


def test_resident_knobs_rejected_by_validate_env(monkeypatch):
    """Satellite: the resident knobs are registered in KNOWN_SPECS, so
    a typo dies loudly at CLI startup (exit 2 contract) instead of
    silently running a default mid-analysis."""
    from mythril_tpu.support.env import EnvSpecError, validate_env

    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_KERNEL", "banana")
    with pytest.raises(EnvSpecError):
        validate_env()
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_KERNEL", "0")
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_BUDGET", "6x6")
    with pytest.raises(EnvSpecError):
        validate_env()
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_BUDGET", "0")
    with pytest.raises(EnvSpecError):
        validate_env()  # below the knob's floor
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_BUDGET", "4096")
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_WATCHDOG", "128")
    monkeypatch.setenv("MYTHRIL_TPU_RESIDENT_EXTRA", "32")
    validate_env()  # sane values pass


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
