"""Async device prefetch (ops/async_dispatch.py): when the profit gate
declines a frontier, the batch launches without blocking and its
results are harvested on a later call — refutations land in the UNSAT
memo + pool nogoods, verified models in ``recent_models``, so repeated
frontier sets are decided host-side for free."""

import numpy as np
import pytest

from mythril_tpu.laser.ethereum.state.constraints import Constraints
from mythril_tpu.smt import UGT, ULT, symbol_factory
from mythril_tpu.smt.solver import get_blast_context, reset_blast_context


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    reset_blast_context()
    from mythril_tpu.ops.async_dispatch import async_stats, get_async_dispatcher
    from mythril_tpu.smt.solver import SolverStatistics

    dispatcher = get_async_dispatcher()
    dispatcher.drop()
    # drain any worker another test file left in flight: launch()
    # declines while a previous worker lives ("never two kernels'
    # worth of prefetch concurrently"), which would fail every launch
    # assertion here depending on file order
    if dispatcher._live_thread is not None:
        dispatcher._live_thread.join(timeout=120)
    async_stats.reset()
    # the adaptive profit gate projects residue cost from the
    # SolverStatistics singleton; native time accumulated by OTHER test
    # files would flip these tests' profit-skip path to a sync dispatch
    SolverStatistics().reset()
    # reach the device path on the CPU jax backend (tests only)
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    # these tests pin the prefetch/dispatch plane BELOW the word tier:
    # hold the tier off so the synthetic lanes actually reach it
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    yield
    get_async_dispatcher().drop()
    reset_blast_context()


def _frontier(tag: str):
    lanes = []
    for i in range(6):
        x = symbol_factory.BitVecSym(f"{tag}{i}", 16)
        if i % 2 == 0:
            lanes.append([x == 3 + i])
        else:  # UNSAT: x < 2 and x > 9
            lanes.append(
                [ULT(x, symbol_factory.BitVecVal(2, 16)),
                 UGT(x, symbol_factory.BitVecVal(9, 16))]
            )
    return lanes


def test_profit_skip_launches_and_harvest_decides_repeats(monkeypatch):
    from mythril_tpu.ops.async_dispatch import async_stats, get_async_dispatcher
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", False)
    monkeypatch.setattr(args, "async_dispatch", True)
    # a fresh analysis has no native_calls, so projected cost is 0 and
    # the profit gate always declines -> async prefetch territory
    dispatch_stats.reset()
    lanes = _frontier("aq")
    batch_check_states([Constraints(lane) for lane in lanes])
    assert dispatch_stats.profit_skips >= 1
    dispatcher = get_async_dispatcher()
    assert async_stats.launches == 1, "profit skip should have prefetched"
    assert dispatcher.pending is not None

    # let the worker thread and the in-flight kernel finish (tests must
    # not depend on timing)
    import time as _time

    deadline = _time.monotonic() + 120
    while not dispatcher.pending["done"]:
        assert _time.monotonic() < deadline, "worker thread never finished"
        _time.sleep(0.05)
    assert not dispatcher.pending.get("failed"), "async launch failed"

    # the SAME frontier re-presents (frontiers repeat across rounds):
    # the harvest at entry memoizes refutations, and this round decides
    # the UNSAT lanes from the memo — no CDCL, no new dispatch
    ctx = get_blast_context()
    verdicts = batch_check_states([Constraints(lane) for lane in lanes])
    assert async_stats.harvested == 1
    assert async_stats.unsat >= 1
    for i, verdict in enumerate(verdicts):
        if i % 2 == 1:
            assert verdict is False, f"lane {i} should come from the memo"
    assert len(ctx.unsat_memo) >= 1


def test_harvested_models_feed_the_probe(monkeypatch):
    """SAT lanes completed by the prefetched kernel must come back as
    verified models in ``recent_models`` (the probe's fuel).  8-bit
    multiplier guards: probe-resistant (so they reach the dispatch
    path) but small enough for the gather DPLL to complete."""
    import time as _time

    from mythril_tpu.ops.async_dispatch import async_stats, get_async_dispatcher
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", False)
    monkeypatch.setattr(args, "async_dispatch", True)
    dispatch_stats.reset()
    odd = symbol_factory.BitVecVal(0x2B, 8)
    lanes = []
    for i in range(6):
        x = symbol_factory.BitVecSym(f"hm{i}", 8)
        lanes.append(
            [(x * odd) == symbol_factory.BitVecVal((0x34 + 37 * i) & 0xFF, 8)]
        )
    ctx = get_blast_context()
    batch_check_states([Constraints(lane) for lane in lanes])
    assert async_stats.launches == 1
    dispatcher = get_async_dispatcher()
    deadline = _time.monotonic() + 120
    while dispatcher.pending and not dispatcher.pending["done"]:
        assert _time.monotonic() < deadline
        _time.sleep(0.05)
    assert not dispatcher.pending.get("failed"), "async launch failed"
    before = len(ctx.recent_models)
    batch_check_states([Constraints(lane) for lane in lanes])
    assert async_stats.harvested == 1
    assert async_stats.models >= 1, "no device models verified"
    # recent_models is truncated to 6 entries (_remember_model keep=6)
    assert len(ctx.recent_models) >= min(before + 1, 6)


def test_async_disabled_by_flag(monkeypatch):
    from mythril_tpu.ops.async_dispatch import async_stats
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", False)
    monkeypatch.setattr(args, "async_dispatch", False)
    dispatch_stats.reset()
    batch_check_states([Constraints(lane) for lane in _frontier("ad")])
    assert async_stats.launches == 0


def test_stale_generation_is_dropped(monkeypatch):
    from mythril_tpu.ops.async_dispatch import async_stats, get_async_dispatcher
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", False)
    monkeypatch.setattr(args, "async_dispatch", True)
    dispatch_stats.reset()
    batch_check_states([Constraints(lane) for lane in _frontier("sg")])
    assert async_stats.launches == 1
    # a context reset (new analysis) must invalidate the pending batch
    reset_blast_context()
    ctx = get_blast_context()
    get_async_dispatcher().harvest(ctx)
    assert async_stats.dropped == 1
    assert async_stats.harvested == 0


def test_prefetch_uses_cone_tier_on_oversized_pool(monkeypatch):
    """The prefetch channel must not go dark when the pool outgrows
    the full-pool gather caps (the steady state of a deep analysis):
    prepare_gather falls back to a union-cone runner, and the harvest
    expands the compact assignment so refutations and models land in
    the memo/probe exactly like full-pool harvests."""
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.ops.async_dispatch import async_stats, get_async_dispatcher
    from mythril_tpu.ops.batched_sat import batch_check_states
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "device_force_dispatch", False)
    monkeypatch.setattr(args, "async_dispatch", True)
    monkeypatch.setattr(args, "batched_solving", True)
    monkeypatch.setattr(args, "device_min_save_s", 1e9)  # always declined
    ctx = get_blast_context()
    for i in range(3):  # push the pool past MAX_GATHER_CLAUSES
        w = symbol_factory.BitVecSym(f"acone_fat{i}", 64)
        ctx.blast_lit(
            (w * symbol_factory.BitVecVal(0x6D2B + 2 * i, 64)
             == symbol_factory.BitVecVal(4321 + i, 64)).raw
        )
    assert ctx.pool.num_clauses > BS.MAX_GATHER_CLAUSES
    from mythril_tpu.laser.ethereum.state.constraints import Constraints

    lanes = _frontier("acone")
    batch_check_states([Constraints(lane) for lane in lanes])
    assert async_stats.launches == 1, "cone-tier prefetch never launched"
    dispatcher = get_async_dispatcher()
    if dispatcher._live_thread is not None:
        dispatcher._live_thread.join(timeout=120)
    dispatcher.harvest(ctx)
    assert async_stats.harvested == 1
    assert async_stats.unsat > 0, "harvest consumed no cone refutations"


# -- preemptible rounds -----------------------------------------------------

V = 255  # vars of the planted CNF below; its pool bucket


def _planted_batch():
    """16 lanes over one planted random 3-CNF (255 vars, 1024 clauses).
    Lanes fix 0-200 vars, to the planted model (even lanes) or at
    random (odd lanes), and lane 15 falsifies a clause outright: after
    GATHER_STEPS they end SAT, UNSAT and undecided, and some decide
    only after the second round."""
    from mythril_tpu.ops.batched_sat import MAX_CLAUSE_WIDTH

    rng = np.random.default_rng(0)
    sol = rng.choice([-1, 1], V + 1)
    rows = []
    while len(rows) < 1024:
        vs = rng.choice(np.arange(2, V + 1), 3, replace=False)
        sg = rng.choice([-1, 1], 3)
        if any(sg == sol[vs]):
            rows.append(list(vs * sg) + [0] * (MAX_CLAUSE_WIDTH - 3))
    lits = np.array(rows, np.int32)
    assign = np.zeros((16, V + 1), np.int8)
    assign[:, :2] = 1  # pad column and the constant-TRUE anchor
    for lane, k in enumerate((0, 0, 0, 0, 10, 20, 40, 60, 80, 100, 120,
                              140, 160, 180, 200)):
        vs = rng.choice(np.arange(2, V + 1), k, replace=False)
        assign[lane, vs] = sol[vs] if lane % 2 == 0 else rng.choice(
            [-1, 1], k)
    for lit in lits[15][:3]:
        assign[15, abs(lit)] = -np.sign(lit)
    return lits, assign


class _Ctx:
    """What the runner and the harvest touch of a blast context; the
    live context's generation, so the batch stays current."""

    def __init__(self):
        self.generation = get_blast_context().generation
        self.solver = type("S", (), {"num_vars": V + 9})
        self.unsat_lanes, self.models = [], []

    def note_unsat(self, node_set):
        self.unsat_lanes.extend(node_set)

    def learn_nogood(self, assumptions, certified=False):
        pass

    def _remember_model(self, env, truth=None):
        self.models.append(truth)


def _prefetch(monkeypatch, tier):
    """A backend whose ``prepare_gather`` runs the planted batch through
    the full-pool runner or the cone runner, and that runner's expected
    one-shot result: ``make_solve_step``'s status and assignment, the
    latter in the full var space the harvest reads."""
    import jax.numpy as jnp

    from mythril_tpu.ops import batched_sat as BS

    lits, assign = _planted_batch()
    backend = BS.BatchedSatBackend()
    ctx = _Ctx()
    if tier == "pool":
        monkeypatch.setattr(backend, "_gather_eligibility",
                            lambda ctx: (None, V))
        monkeypatch.setattr(backend, "_sync_pool_and_assign",
                            lambda ctx, sets, num_vars: assign)
        backend.pool.num_vars, backend.pool.lits = V, jnp.asarray(lits)
        full, status = BS.make_solve_step(V)(lits, assign)
        status, full = np.asarray(status), np.asarray(full)
    else:
        cone_vars = np.arange(V) + 9  # compact column j + 1 is var j + 9
        monkeypatch.setattr(backend, "_gather_eligibility",
                            lambda ctx: ("size_bailouts", V))
        monkeypatch.setattr(backend, "_build_cone_batch",
                            lambda ctx, sets: (lits, assign, cone_vars, ()))
        bucket = BS.DevicePool._bucket(V)
        padded = np.ones((16, bucket + 1), np.int8)
        padded[:, :V + 1] = assign
        compact, status = BS.make_solve_step(bucket)(lits, padded)
        status = np.asarray(status)
        full = np.zeros((16, V + 10), np.int8)
        full[:, cone_vars] = np.asarray(compact)[:, 1:V + 1]
    monkeypatch.setattr(BS, "_env_from_assignment", lambda ctx, row: {})
    return backend, ctx, status, full


def _launch(dispatcher, backend, ctx):
    lanes = range(16)
    assert dispatcher.launch(backend, ctx, [[lane + 2] for lane in lanes],
                             [[lane] for lane in lanes],
                             [[] for _ in lanes])


@pytest.mark.parametrize("tier", ["pool", "cone"])
def test_sliced_prefetch_is_bit_identical_to_the_one_shot_solve(
        monkeypatch, tier):
    """Run to its last round, the prefetch's ladder of 64-step rounds
    gives ``make_solve_step``'s statuses and assignments, bit for bit,
    on lanes that end SAT, UNSAT and undecided."""
    from mythril_tpu.ops.async_dispatch import AsyncDispatcher, async_stats
    from mythril_tpu.ops.batched_sat import GATHER_ROUND_BUDGETS, GATHER_STEPS

    backend, ctx, status, full = _prefetch(monkeypatch, tier)
    assert {0, 1, 2} <= set(status.tolist())
    dispatcher = AsyncDispatcher()
    _launch(dispatcher, backend, ctx)
    dispatcher._live_thread.join(timeout=120)
    assert not dispatcher._live_thread.is_alive()
    pending = dispatcher.pending
    assert pending["done"] and not pending.get("failed")
    assert async_stats.rounds == GATHER_STEPS // GATHER_ROUND_BUDGETS[0]
    assert async_stats.preempted == 0
    np.testing.assert_array_equal(pending["status"], status)
    np.testing.assert_array_equal(pending["assign"], full)


@pytest.mark.parametrize("waiter", ["analysis_thread", "none",
                                    "worker_thread"])
def test_a_device_wait_cuts_the_prefetch_at_its_next_round(monkeypatch,
                                                           waiter):
    """A ``device.wait`` entered on the analysis thread while the
    worker runs round 2 stops the worker after that round: one batch
    preempted, counted on the launching analysis's record, and the
    harvest takes only the lanes two rounds decided.  With no wait, or
    a wait on the worker's own thread, every round runs and the harvest
    is the one-shot solve's."""
    import threading

    from mythril_tpu.observability import spans
    from mythril_tpu.ops.async_dispatch import AsyncDispatcher, async_stats
    from mythril_tpu.ops.batched_sat import GATHER_ROUND_BUDGETS, GATHER_STEPS

    backend, ctx, status, _full = _prefetch(monkeypatch, "pool")
    # the statuses after each round, from an unpreempted ladder
    per_round = [np.asarray(h["status"]) for h in backend._prefetch_rounds(
        V, backend.pool.lits, _planted_batch()[1])]
    at_cut = np.where(per_round[1] == 3, 0, per_round[1])
    decided = set(np.nonzero(status)[0])
    assert set(np.nonzero(at_cut)[0]) < decided  # the cut costs lanes

    in_round_2, go = threading.Event(), threading.Event()
    real = backend._cached_round
    calls = []

    def gated(bucket, budget):
        step = real(bucket, budget)

        def run(*state):
            calls.append(None)
            if len(calls) == 2:
                in_round_2.set()
                assert go.wait(timeout=60)
                if waiter == "worker_thread":
                    with spans.wait("step_lock"):
                        pass
            return step(*state)

        return run

    monkeypatch.setattr(backend, "_cached_round", gated)
    dispatcher = AsyncDispatcher()
    with spans.analysis("cli.analyze"):
        _launch(dispatcher, backend, ctx)
        assert in_round_2.wait(timeout=120)
        if waiter == "analysis_thread":
            with spans.wait("replay.upload"):
                pass
        go.set()
        dispatcher._live_thread.join(timeout=120)
        assert not dispatcher._live_thread.is_alive()
    record = spans.analysis_profiles()[-1]
    dispatcher.harvest(ctx)
    assert async_stats.harvested == 1

    cut = waiter == "analysis_thread"
    expected = at_cut if cut else status
    assert async_stats.preempted == record["prefetch_preempted"] == cut
    assert async_stats.rounds == (
        2 if cut else GATHER_STEPS // GATHER_ROUND_BUDGETS[0])
    assert ctx.unsat_lanes == np.nonzero(expected == 2)[0].tolist()
    assert len(ctx.models) == int((expected == 1).sum())
