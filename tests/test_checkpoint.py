"""Preemption-safety tests: the durable checkpoint/resume plane,
graceful drain, and poisoned-lane quarantine.

Tier-1 under the existing ``faults`` marker (same chaos discipline as
tests/test_faults.py): everything runs the single-device gather path
on CPU, injected failures are deterministic, and the invariant under
test is always the same one the resilience package promises — a
killed-and-resumed, drained, or lane-quarantined analysis reports
findings identical to the uninterrupted fault-free run.

The cross-process half of the story (SIGKILL at every injection point,
resume in a fresh interpreter) lives in ``scripts/chaos_corpus.py
--kill-resume``; these tests pin the in-process mechanics: journal
format (atomicity, CRC/version rejection, generation retention),
boundary/cadence/demotion-nudge write policy, drain-to-partial-report,
channel freeze/thaw, and the bisection isolating exactly the poisoned
lane.
"""

import json
import os
import pickle
import signal as signal_module
import struct
import time

import pytest

from mythril_tpu.laser.ethereum.state.constraints import Constraints
from mythril_tpu.resilience import checkpoint as cp
from mythril_tpu.resilience import faults, watchdog
from mythril_tpu.resilience.telemetry import resilience_stats
from mythril_tpu.smt import UGT, ULT, symbol_factory
from mythril_tpu.smt.solver import get_blast_context, reset_blast_context

pytestmark = pytest.mark.faults

EXEC_TIMEOUT = 60


@pytest.fixture(autouse=True)
def ckpt_env(monkeypatch):
    """Single-device gather path, forced dispatch, probing off, clean
    fault/watchdog/checkpoint state on both sides of each test (the
    chaos_env discipline from test_faults.py plus the checkpoint
    plane)."""
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
    monkeypatch.setattr(args, "checkpoint_dir", None)
    monkeypatch.setattr(args, "resume_from", None)
    faults.reset_for_tests()
    watchdog.reset_for_tests()
    cp.reset_for_tests()
    from mythril_tpu.ops.async_dispatch import get_async_dispatcher
    from mythril_tpu.smt.solver import SolverStatistics

    get_async_dispatcher().drop()
    SolverStatistics().reset()
    yield
    faults.reset_for_tests()
    watchdog.reset_for_tests()
    cp.reset_for_tests()
    from mythril_tpu.ops import device_health

    device_health.reset_for_tests()
    reset_blast_context()


def _analyze():
    """Full pipeline over the chaos contract; returns (found_swcs,
    telemetry row)."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import fire_lasers
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.laser.ethereum.time_handler import time_handler
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.solidity.evmcontract import EVMContract
    from mythril_tpu.support.model import clear_model_cache

    import bench

    reset_blast_context()
    clear_model_cache()
    for module in ModuleLoader().get_detection_modules():
        module.reset_module()
        module.cache.clear()
    dispatch_stats.reset()
    time_handler.start_execution(EXEC_TIMEOUT)
    sym = SymExecWrapper(
        EVMContract(code=bench.chaos_tree_contract(), name="ckpt"),
        address=0x901D12EBE1B195E5AA8748E62BD7734AE19B51F,
        strategy="bfs",
        max_depth=128,
        execution_timeout=EXEC_TIMEOUT,
        create_timeout=10,
        transaction_count=1,
    )
    issues = fire_lasers(sym)
    return {i.swc_id for i in issues}, dispatch_stats.as_dict()


_baseline_cache = {}


def _baseline():
    if "found" not in _baseline_cache:
        found, row = _analyze()
        _baseline_cache["found"] = found
        _baseline_cache["row"] = row
    return _baseline_cache["found"], _baseline_cache["row"]


# ---------------------------------------------------------------------------
# journal file format: atomic write, retention, corruption rejection
# ---------------------------------------------------------------------------


def test_journal_round_trip_and_retention(tmp_path):
    d = str(tmp_path)
    for n in range(3):
        cp.write_journal(d, {"generation_payload": n})
    kept = cp._generations(d)
    assert len(kept) == cp.JOURNAL_KEEP, kept
    assert cp.load_journal(d) == {"generation_payload": 2}
    assert not os.path.exists(os.path.join(d, ".journal.tmp"))


def test_corrupt_newest_falls_back_one_generation(tmp_path):
    d = str(tmp_path)
    cp.write_journal(d, {"n": 1})
    newest = cp.write_journal(d, {"n": 2})
    with open(newest, "r+b") as fh:
        fh.seek(-1, os.SEEK_END)
        fh.write(b"\xff")
    assert cp.load_journal(d) == {"n": 1}


def test_corrupt_fallback_is_counted_and_warned(tmp_path, caplog):
    """The fallback to an older generation must be LOUD: a structured
    warning plus the checkpoint_corrupt_fallbacks counter, not a silent
    resume from stale state."""
    import logging

    d = str(tmp_path)
    cp.write_journal(d, {"n": 1})
    newest = cp.write_journal(d, {"n": 2})
    with open(newest, "r+b") as fh:
        fh.seek(-1, os.SEEK_END)
        fh.write(b"\xff")
    before = resilience_stats.checkpoint_corrupt_fallbacks
    with caplog.at_level(logging.WARNING,
                         logger="mythril_tpu.resilience.checkpoint"):
        assert cp.load_journal(d) == {"n": 1}
    assert resilience_stats.checkpoint_corrupt_fallbacks == before + 1
    messages = [r.getMessage() for r in caplog.records]
    assert any("corrupt journal" in m for m in messages), messages
    assert any("OLDER generation" in m for m in messages), messages


def test_every_generation_corrupt_raises_loudly(tmp_path):
    d = str(tmp_path)
    for n in range(2):
        cp.write_journal(d, {"n": n})
    for _, path in cp._generations(d):
        with open(path, "r+b") as fh:
            fh.seek(-1, os.SEEK_END)
            fh.write(b"\xff")
    with pytest.raises(cp.JournalCorrupt):
        cp.load_journal(d)


def test_stale_version_is_rejected(tmp_path):
    d = str(tmp_path)
    path = cp.write_journal(d, {"n": 0})
    with open(path, "r+b") as fh:
        fh.seek(len(cp.JOURNAL_MAGIC))
        fh.write(struct.pack("<I", cp.JOURNAL_VERSION + 1))
    with pytest.raises(cp.JournalCorrupt, match="version"):
        cp.load_journal(d)


def test_truncated_body_is_rejected(tmp_path):
    d = str(tmp_path)
    path = cp.write_journal(d, {"payload": list(range(100))})
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(size - 7)
    with pytest.raises(cp.JournalCorrupt, match="truncated|CRC"):
        cp.load_journal(d)


def test_empty_directory_loads_none(tmp_path):
    assert cp.load_journal(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# plane policy: boundary writes, cadence, demotion nudge, target check
# ---------------------------------------------------------------------------


class _FakeLaser:
    def __init__(self, transaction_count=1):
        self.open_states = []
        self.transaction_count = transaction_count


def test_plane_cadence_and_demotion_nudge(tmp_path, monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_CHECKPOINT_PERIOD", "9999")
    plane = cp.get_checkpoint_plane()
    plane.configure(str(tmp_path))
    plane.transaction_boundary(_FakeLaser(), 0xABC, 0)
    assert len(cp._generations(str(tmp_path))) == 1
    plane.tick()  # inside the cadence window: no write
    assert len(cp._generations(str(tmp_path))) == 1
    plane.note_demotion()  # a demotion forces the next tick to write
    plane.tick()
    assert len(cp._generations(str(tmp_path))) == 2
    assert resilience_stats.checkpoints_written >= 2
    assert resilience_stats.checkpoint_s >= 0.0


def test_process_demotion_rides_the_journal(tmp_path):
    """A demotion decided in-process (from a dispatch's own error)
    lands in the journal with its reason, so a resumed run starts
    demoted instead of re-dispatching to a lost device."""
    from mythril_tpu.ops import device_health

    device_health.mark_unhealthy("gather dispatch failed: UNAVAILABLE")
    plane = cp.get_checkpoint_plane()
    plane.configure(str(tmp_path))
    plane.transaction_boundary(_FakeLaser(), 0xABC, 0)
    assert cp.load_journal(str(tmp_path))["device_demoted"] == (
        "gather dispatch failed: UNAVAILABLE"
    )


def test_resume_rejects_mismatched_target(tmp_path):
    plane = cp.get_checkpoint_plane()
    plane.configure(str(tmp_path))
    plane.transaction_boundary(_FakeLaser(transaction_count=1), 0xABC, 0)
    cp.reset_for_tests()
    plane = cp.get_checkpoint_plane()
    plane.configure(str(tmp_path), resume=True)
    # same dir, different analysis target: must start fresh, not
    # graft another contract's frontier onto this run
    other = _FakeLaser(transaction_count=3)
    assert plane.restore_transactions(other, 0xDEF) == 0


def test_checkpoint_period_env_parsing(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_CHECKPOINT_PERIOD", "0")
    assert cp.checkpoint_period_s() == 0.0
    monkeypatch.setenv("MYTHRIL_TPU_CHECKPOINT_PERIOD", "bogus")
    assert cp.checkpoint_period_s() == cp.DEFAULT_PERIOD_S
    monkeypatch.delenv("MYTHRIL_TPU_CHECKPOINT_PERIOD")
    assert cp.checkpoint_period_s() == cp.DEFAULT_PERIOD_S


# ---------------------------------------------------------------------------
# solver channel freeze/thaw (the verdict-preserving resume channels)
# ---------------------------------------------------------------------------


def test_channel_freeze_thaw_survives_pickling():
    reset_blast_context()
    ctx = get_blast_context()
    x = symbol_factory.BitVecSym("ckch0", 16)
    lo = ULT(x, symbol_factory.BitVecVal(2, 16)).raw
    hi = UGT(x, symbol_factory.BitVecVal(9, 16)).raw
    ctx.unsat_memo[tuple(sorted((lo.id, hi.id)))] = True
    from mythril_tpu.smt import terms as T

    env = T.EvalEnv(variables={x.raw.id: 5}, arrays={}, ufs={},
                    array_default=0)
    ctx.probe_memo[(lo.id,)] = env
    ctx.recent_models = [env]
    frozen = cp.freeze_channels(ctx)
    cp._install_reducers()
    blob = pickle.dumps(frozen, protocol=4)
    # the resume flow: the interner forgets everything (fresh process),
    # the journal unpickles FIRST (nodes re-intern with fresh ids), and
    # the analysis's structurally-identical constraints then intern to
    # those same nodes — so the thawed id-keys keep hitting
    reset_blast_context()
    ctx2 = get_blast_context()
    cp.thaw_channels(ctx2, pickle.loads(blob))
    x2 = symbol_factory.BitVecSym("ckch0", 16)
    lo2 = ULT(x2, symbol_factory.BitVecVal(2, 16)).raw
    hi2 = UGT(x2, symbol_factory.BitVecVal(9, 16)).raw
    assert tuple(sorted((lo2.id, hi2.id))) in ctx2.unsat_memo
    assert (lo2.id,) in ctx2.probe_memo
    assert ctx2.probe_memo[(lo2.id,)].variables[x2.raw.id] == 5
    assert len(ctx2.recent_models) == 1


# ---------------------------------------------------------------------------
# graceful drain: signal -> flag -> partial report -> resumable journal
# ---------------------------------------------------------------------------


def test_sigterm_sets_the_drain_flag():
    old_term = signal_module.getsignal(signal_module.SIGTERM)
    old_int = signal_module.getsignal(signal_module.SIGINT)
    try:
        cp.install_signal_handlers()
        assert not cp.drain_requested()
        os.kill(os.getpid(), signal_module.SIGTERM)
        deadline = time.monotonic() + 5.0
        while not cp.drain_requested():
            assert time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        cp._handlers_installed = False
        signal_module.signal(signal_module.SIGTERM, old_term)
        signal_module.signal(signal_module.SIGINT, old_int)
        cp.reset_for_tests()


def test_drained_report_flags_partial():
    from mythril_tpu.analysis.report import Report

    resilience_stats.reset()
    cp.request_drain("test")
    payload = json.loads(Report().as_swc_standard_format())
    assert payload[0]["meta"]["resilience"]["partial"] is True
    cp.reset_for_tests()
    resilience_stats.reset()
    payload = json.loads(Report().as_swc_standard_format())
    assert "resilience" not in payload[0]["meta"]


def test_drain_mid_analysis_then_resume_restores_findings(
    tmp_path, monkeypatch
):
    """The drain + resume contract end to end: a drain landing in the
    middle of a transaction stops the analysis at the next cooperative
    checkpoint with a final journal generation, the report says
    partial, and a resumed run re-executes the interrupted transaction
    to findings identical to the uninterrupted baseline."""
    base_found, _ = _baseline()
    from mythril_tpu.analysis.report import Report
    from mythril_tpu.support.support_args import args

    monkeypatch.setenv("MYTHRIL_TPU_CHECKPOINT_PERIOD", "0")
    monkeypatch.setattr(args, "checkpoint_dir", str(tmp_path))
    plane = cp.get_checkpoint_plane()
    orig_tick = plane.tick
    ticks = []

    def tick_then_drain():
        orig_tick()
        ticks.append(1)
        if len(ticks) == 3:  # mid-first-transaction, deterministically
            cp.request_drain("test")

    monkeypatch.setattr(plane, "tick", tick_then_drain)
    _analyze()
    assert cp.drain_requested()
    assert plane.partial is True
    generations = cp._generations(str(tmp_path))
    assert generations, "drain landed no final checkpoint"
    payload = json.loads(Report().as_swc_standard_format())
    assert payload[0]["meta"]["resilience"]["partial"] is True
    # the journal must hold the interrupted transaction's START
    # boundary: resuming re-executes it in full
    assert cp.load_journal(str(tmp_path))["tx_index"] == 0

    cp.reset_for_tests()  # fresh plane + cleared drain flag
    monkeypatch.setattr(args, "resume_from", str(tmp_path))
    found, row = _analyze()
    assert found == base_found, (found, base_found)
    assert row["resumes"] == 1


def test_kill_at_spec_validated_at_startup(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_KILL_AT", "not_a_point")
    faults.reset_for_tests()
    with pytest.raises(faults.FaultSpecError):
        faults.get_fault_plane()
    monkeypatch.delenv("MYTHRIL_TPU_KILL_AT")
    faults.reset_for_tests()


# ---------------------------------------------------------------------------
# poisoned-lane bisection: quarantine one lane, keep the context
# ---------------------------------------------------------------------------


def _frontier(tag: str):
    """6 lanes: even = satisfiable multiplier guards (probe-resistant),
    odd = UNSAT interval contradictions."""
    lanes = []
    odd = symbol_factory.BitVecVal(0x2B, 16)
    for i in range(6):
        x = symbol_factory.BitVecSym(f"{tag}{i}", 16)
        if i % 2 == 0:
            lanes.append(
                [(x * odd) == symbol_factory.BitVecVal(
                    (0x34 + 37 * i) & 0xFFFF, 16)]
            )
        else:
            lanes.append(
                [ULT(x, symbol_factory.BitVecVal(2, 16)),
                 UGT(x, symbol_factory.BitVecVal(9, 16))]
            )
    return [Constraints(lane) for lane in lanes]


def test_bisection_quarantines_exactly_the_poisoned_lane(monkeypatch):
    """A lane-dependent repeatable dispatch failure must cost ONE lane
    (to the CDCL tail), not the context: quarantined_lanes == 1,
    demotions unchanged, every decided verdict identical to the clean
    run, and later batches still dispatch on device."""
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats

    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_BACKOFF_S", "0.01")
    dispatch_stats.reset()
    clean = batch_check_states(_frontier("bq"))
    assert dispatch_stats.dispatches > 0, "frontier never dispatched"
    reset_blast_context()
    dispatch_stats.reset()
    faults.get_fault_plane().arm("lane_poison", times=99, lane=2)
    poisoned = batch_check_states(_frontier("bp"))
    assert resilience_stats.quarantined_lanes == 1, (
        "bisection must isolate exactly the poisoned lane"
    )
    assert resilience_stats.bisect_dispatches >= 2
    assert resilience_stats.demotions == 0, (
        "quarantine must not demote the context"
    )
    assert dispatch_stats.fused is False
    for i, verdict in enumerate(poisoned):
        # the quarantined lane may only fall undecided (the CDCL tail
        # re-solves it); no verdict may ever flip
        if verdict is not None:
            assert verdict == clean[i], (i, verdict, clean[i])
    # the context stays on device: a fresh batch still dispatches
    faults.reset_for_tests()
    dispatch_stats.reset()
    batch_check_states(_frontier("bz"))
    assert dispatch_stats.dispatches > 0, (
        "context was knocked off device by a single-lane quarantine"
    )


def test_lane_poison_requires_a_lane():
    with pytest.raises(faults.FaultSpecError):
        faults.get_fault_plane().arm("lane_poison", times=1)


def test_non_lane_failure_still_escalates_to_demotion(monkeypatch):
    """When every lane fails alone the failure is not lane-dependent:
    the ladder must fall through to the classic context demotion, not
    quarantine the whole batch one lane at a time."""
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats

    base_found_unused = None  # frontier-level: no findings oracle here
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_BACKOFF_S", "0.01")
    # the escalation needs every lane to reach the (faulted) device:
    # hold the word tier off so none retire pre-dispatch
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    reset_blast_context()
    dispatch_stats.reset()
    faults.get_fault_plane().arm("dispatch_error", times=999)
    verdicts = batch_check_states(_frontier("de"))
    assert resilience_stats.demotions >= 1
    assert dispatch_stats.fused is True
    assert verdicts == [None] * len(verdicts) or all(
        v is None for v in verdicts
    )


# ---------------------------------------------------------------------------
# watchdog latency-table bound (satellite)
# ---------------------------------------------------------------------------


def test_ewma_table_is_bounded_with_lru_eviction(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_EWMA_CAP", "16")
    dog = watchdog.DispatchWatchdog()
    for i in range(100):
        dog.observe(f"gather:{i}", 0.1)
    assert len(dog._ewma) <= 16
    # recency, not insertion order: a key kept hot through
    # deadline_for() must survive eviction waves of colder keys
    dog.observe("hot", 0.2)
    for i in range(100, 140):
        dog.deadline_for("hot")
        dog.observe(f"gather:{i}", 0.1)
    assert "hot" in dog._ewma
    assert len(dog._ewma) <= 16
    monkeypatch.setenv("MYTHRIL_TPU_EWMA_CAP", "bogus")
    assert watchdog.ewma_cap() == watchdog.EWMA_CAP
    monkeypatch.setenv("MYTHRIL_TPU_EWMA_CAP", "2")
    assert watchdog.ewma_cap() == 8  # floored: eviction quarter >= 2


def test_ewma_table_covers_resident_key_family(monkeypatch):
    """The resident solver's `resident:{bucket}` keys live in the same
    LRU-bounded table as the ladder's per-budget keys: one key per
    lane bucket (no per-round proliferation), recency-kept under
    pressure from ladder-key churn, and subject to the same cap."""
    monkeypatch.setenv("MYTHRIL_TPU_EWMA_CAP", "16")
    dog = watchdog.DispatchWatchdog()
    # the whole resident family a real run can produce: one key per
    # power-of-two lane bucket — this NEVER grows with pool shape or
    # round budget, which is the point of the satellite
    for bucket in (4, 8, 16, 32, 64, 128):
        dog.observe(f"resident:{bucket}", 0.5)
    assert len(dog._ewma) == 6
    # ladder-key churn (the proliferating family the resident kernel
    # replaces) must not evict a resident key that stays hot
    for i in range(100):
        dog.deadline_for("resident:8")
        dog.observe(f"frontier:{i}", 0.1)
    assert "resident:8" in dog._ewma
    assert len(dog._ewma) <= 16
    # a warm resident key budgets its own deadline from its own EWMA,
    # not the cold-key cap
    warm = dog.deadline_for("resident:8")
    assert warm < dog.deadline_for("resident:256")  # cold: full cap
