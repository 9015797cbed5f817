"""Chaos tests: the escalation ladder under injected faults.

Every test drives a REAL corpus-style analysis (or the frontier batch
path it is built from) with a fault armed on the resilience plane, and
asserts the two invariants the ladder exists for:

1. the analysis terminates within its deadline budget and reports
   **identical SWC findings** to the fault-free run (degradation never
   changes results, only who computes them);
2. the matching degradation counter (`watchdog_trips`,
   `dispatch_retries`, `demotions`, `unhealthy_skips`) incremented, so
   the degraded run is attributable from telemetry alone.

Deliberately tier-1 (``not slow``): injected deadlines stay under 2 s
(`MYTHRIL_TPU_DISPATCH_TIMEOUT=0.4`, hangs of 1.0 s), and the analyses
run the single-chip gather path — the virtual 8-device mesh would
recompile a shard_map per pool bucket, which buys the chaos semantics
nothing.
"""

import threading
import time

import pytest

from mythril_tpu.laser.ethereum.state.constraints import Constraints
from mythril_tpu.resilience import faults, watchdog
from mythril_tpu.resilience.telemetry import resilience_stats
from mythril_tpu.smt import UGT, ULT, symbol_factory
from mythril_tpu.smt.solver import get_blast_context, reset_blast_context

pytestmark = pytest.mark.faults

EXEC_TIMEOUT = 60


def _chaos_contract() -> str:
    """Depth-2 selector-bit dispatch tree with multiplier-guard leaves
    (probe-resistant, so lanes genuinely reach the device) and one
    SWC-106 suicide leaf as the findings oracle — shared with the soak
    driver via bench.chaos_tree_contract."""
    import bench

    return bench.chaos_tree_contract()


@pytest.fixture(autouse=True)
def chaos_env(monkeypatch):
    """Single-device gather path, forced dispatch, probing off (so
    frontier lanes survive to the device), clean fault/watchdog state
    on both sides of each test."""
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
    # chaos tests pin the ladder/fuse semantics per dispatch: the
    # coalescer's admission window (its own tests live in
    # test_sweep_scheduler.py) must not swallow calls here
    monkeypatch.setattr(args, "device_coalesce", False)
    # de-flake the fault-free assertions: a warm-key dispatch that
    # hits a >5s hiccup (an XLA recompile for a grown pool shape under
    # the same watchdog key, GC, CI noise — observed on the base tree
    # deep into a full-suite process) would trip the 5s warm-deadline
    # floor and fail the baseline's watchdog_trips==0.  Raising the
    # floor here changes nothing for the trip tests: every one of them
    # pins an explicit MYTHRIL_TPU_DISPATCH_TIMEOUT cap (0.1-0.4s)
    # that dominates the floor via min(cap, max(floor, ewma*mult)).
    monkeypatch.setattr(watchdog, "DEADLINE_FLOOR_S", 60.0)
    faults.reset_for_tests()
    watchdog.reset_for_tests()
    from mythril_tpu.ops.async_dispatch import get_async_dispatcher
    from mythril_tpu.smt.solver import SolverStatistics

    get_async_dispatcher().drop()
    SolverStatistics().reset()
    yield
    faults.reset_for_tests()
    watchdog.reset_for_tests()
    # an injected probe flap or device loss demotes the process —
    # undo it for the rest of the suite
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

    reset_blast_context()
    clear_model_cache()
    for module in ModuleLoader().get_detection_modules():
        module.reset_module()
        module.cache.clear()
    dispatch_stats.reset()
    time_handler.start_execution(EXEC_TIMEOUT)
    sym = SymExecWrapper(
        EVMContract(code=_chaos_contract(), name="chaos"),
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
    """Fault-free reference findings, computed once per session (also
    warms the jit caches the faulted runs reuse)."""
    if "found" not in _baseline_cache:
        found, row = _analyze()
        _baseline_cache["found"] = found
        _baseline_cache["row"] = row
    return _baseline_cache["found"], _baseline_cache["row"]


# ---------------------------------------------------------------------------
# end-to-end: each injected fault vs the fault-free findings
# ---------------------------------------------------------------------------


def test_fault_free_baseline_dispatches_and_is_clean():
    found, row = _baseline()
    assert "106" in found, found
    assert row["dispatches"] > 0, (
        "chaos contract no longer reaches the device — every fault "
        "test below would be vacuous"
    )
    assert row["watchdog_trips"] == 0
    assert row["demotions"] == 0


def test_dispatch_hang_trips_watchdog_and_demotes(monkeypatch):
    base_found, _ = _baseline()
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "0.4")
    faults.get_fault_plane().arm("dispatch_hang", times=99, hang_s=1.0)
    began = time.monotonic()
    found, row = _analyze()
    wall = time.monotonic() - began
    assert found == base_found, (found, base_found)
    assert row["watchdog_trips"] >= 1
    assert row["demotions"] >= 1
    assert row["fused"] is True  # context demoted to the CDCL tail
    assert row["dispatches"] == 0  # nothing engaged past the hang
    # deadline budget: 3 attempts x 0.4s + backoff, then pure CDCL —
    # nowhere near the 30s an unsupervised hang would cost per dispatch
    assert wall < 20, wall


def test_dispatch_error_once_is_retried_and_recovers():
    base_found, _ = _baseline()
    faults.get_fault_plane().arm("dispatch_error", times=1)
    found, row = _analyze()
    assert found == base_found
    assert row["dispatch_retries"] >= 1
    assert row["demotions"] == 0, "one transient error must not demote"
    assert row["dispatches"] > 0, "the retry should have recovered"


def test_dispatch_error_exhaustion_demotes(monkeypatch):
    base_found, _ = _baseline()
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_BACKOFF_S", "0.01")
    faults.get_fault_plane().arm("dispatch_error", times=99)
    found, row = _analyze()
    assert found == base_found
    assert row["dispatch_retries"] >= 2
    assert row["demotions"] >= 1
    assert row["fused"] is True
    assert row["dispatches"] == 0


def test_probe_flap_mid_run_degrades_to_unhealthy_skips():
    base_found, _ = _baseline()
    # skip=1: the first dispatch's device check passes, the flap lands
    # mid-analysis — a device lost after a healthy start
    faults.get_fault_plane().arm("probe_flap", times=1, skip=1)
    found, row = _analyze()
    assert found == base_found
    assert row["unhealthy_skips"] >= 1
    from mythril_tpu.ops.device_health import demotion, device_ok

    assert device_ok() is False  # demotion lasts for the process
    assert demotion() == "injected probe flap"  # the warning names it


def test_cdcl_raise_is_retried_and_findings_survive():
    base_found, _ = _baseline()
    faults.get_fault_plane().arm("cdcl_error", times=1)
    found, row = _analyze()
    assert found == base_found
    assert row["dispatch_retries"] >= 1


# ---------------------------------------------------------------------------
# frontier-level checks (cheap): garbage lanes, prefetch faults
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


def test_garbage_lanes_are_rejected_by_host_verification():
    """Corrupted device output claims every lane is a SAT candidate
    over a garbage assignment: host model verification must reject the
    garbage, never decide a lane wrongly, and leave the residue to the
    CDCL tail."""
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats

    dispatch_stats.reset()
    clean = batch_check_states(_frontier("gc"))
    reset_blast_context()
    dispatch_stats.reset()
    faults.get_fault_plane().arm("dispatch_garbage", times=99)
    corrupted = batch_check_states(_frontier("gd"))
    assert resilience_stats.faults_fired >= 1, "garbage fault never fired"
    assert dispatch_stats.sat_verified == 0, (
        "a garbage assignment passed host verification"
    )
    for i, verdict in enumerate(corrupted):
        # garbage may only cost decisions (None -> CDCL tail), never
        # flip one: any non-None verdict must match the clean run's
        if verdict is not None:
            assert verdict == clean[i], (i, verdict, clean[i])


def test_prefetch_fault_drops_the_batch(monkeypatch):
    from mythril_tpu.ops.async_dispatch import async_stats, get_async_dispatcher
    from mythril_tpu.ops.batched_sat import batch_check_states, dispatch_stats
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_force_dispatch", False)
    monkeypatch.setattr(args, "async_dispatch", True)
    dispatch_stats.reset()
    async_stats.reset()
    faults.get_fault_plane().arm("prefetch_error", times=1)
    dispatcher = get_async_dispatcher()
    if dispatcher._live_thread is not None:
        dispatcher._live_thread.join(timeout=120)
    batch_check_states(_frontier("pf"))
    assert async_stats.launches == 1
    deadline = time.monotonic() + 60
    while dispatcher.pending is not None and not dispatcher.pending["done"]:
        assert time.monotonic() < deadline
        time.sleep(0.02)
    assert dispatcher.pending is not None and dispatcher.pending["failed"]
    dispatcher.harvest(get_blast_context())
    assert async_stats.dropped == 1
    assert async_stats.harvested == 0


def test_wedged_prefetch_is_abandoned_at_deadline(monkeypatch):
    """A pending batch older than the dispatch deadline cap is dropped
    at harvest (the worker stays parked; the channel goes dark instead
    of the analysis)."""
    from mythril_tpu.ops.async_dispatch import (
        AsyncDispatcher, async_stats,
    )

    async_stats.reset()
    resilience_stats.reset()
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "0.1")
    dispatcher = AsyncDispatcher()
    ctx = get_blast_context()
    dispatcher.pending = {
        "generation": ctx.generation,
        "done": False,
        "began": time.monotonic() - 5.0,
    }
    dispatcher.harvest(ctx)
    assert dispatcher.pending is None
    assert async_stats.dropped == 1
    assert resilience_stats.watchdog_trips == 1
    assert resilience_stats.demotions == 1


# ---------------------------------------------------------------------------
# unit-level: ladder mechanics, env parsing, shutdown join
# ---------------------------------------------------------------------------


def test_watchdog_deadline_follows_the_latency_ewma(monkeypatch):
    dog = watchdog.DispatchWatchdog()
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "100")
    assert dog.deadline_for("k") == 100.0  # cold key: the full cap
    dog.observe("k", 0.2)
    # warm: EWMA * mult, floored
    assert dog.deadline_for("k") == pytest.approx(
        max(watchdog.DEADLINE_FLOOR_S, 0.2 * watchdog.DEADLINE_MULT)
    )
    for _ in range(20):
        dog.observe("k", 30.0)
    assert dog.deadline_for("k") == 100.0  # capped
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "0.3")
    assert dog.deadline_for("k") == 0.3  # operator cap always wins


@pytest.mark.parametrize("error, lost", [
    ("UNAVAILABLE: TPU device lost", True),
    ("DATA_LOSS: device memory corrupted", True),
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem", False),
    ("INTERNAL: Mosaic failed to compile TPU kernel", False),
])
def test_ladder_demotes_process_only_on_device_loss(monkeypatch, error,
                                                     lost):
    """Last rung: retries exhausted -> the context always demotes; the
    whole process demotes only when the failing dispatch's OWN error
    says the device is gone (decided in-process, no child probe)."""
    from mythril_tpu.ops import device_health

    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_BACKOFF_S", "0.01")
    resilience_stats.reset()

    def fail():
        raise RuntimeError(error)

    dog = watchdog.DispatchWatchdog()
    with pytest.raises(watchdog.DispatchAbandoned) as exc_info:
        dog.supervised("k", fail)
    assert exc_info.value.process_demoted is lost
    assert device_health.device_ok() is (not lost)
    if lost:
        assert error in device_health.demotion()
    assert resilience_stats.demotions == 1
    assert resilience_stats.dispatch_retries == 2


def test_compile_time_extends_the_deadline_and_skips_the_ewma(
    monkeypatch
):
    """Time a supervised worker spends inside a jax compile is set-up,
    not a hung device: it extends the deadline and stays out of the
    latency model.  The same wait outside a compile trips."""
    import jax.monitoring

    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "0.3")
    event = "/jax/core/compile/backend_compile_duration"

    def compile_then_run():
        jax.monitoring.record_scalar(event, time.time())
        time.sleep(0.6)
        jax.monitoring.record_event_duration_secs(event, 0.6)
        return "ran"

    dog = watchdog.DispatchWatchdog()
    assert dog.run("k", compile_then_run) == "ran"
    assert dog._ewma["k"] < 0.3
    with pytest.raises(watchdog.WatchdogTimeout):
        dog.run("k2", lambda: time.sleep(0.6))


def test_ladder_hang_demotes_the_context_not_the_process(monkeypatch):
    """A deadline miss on every attempt is local to its dispatch: the
    context demotes, the process stays on the device."""
    from mythril_tpu.ops import device_health

    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "0.2")
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_BACKOFF_S", "0.01")
    resilience_stats.reset()
    dog = watchdog.DispatchWatchdog()
    with pytest.raises(watchdog.DispatchAbandoned) as exc_info:
        dog.supervised("k", lambda: time.sleep(5))
    assert exc_info.value.process_demoted is False
    assert device_health.device_ok() is True
    assert resilience_stats.demotions == 1
    assert resilience_stats.watchdog_trips == 3


def test_cancellation_checkpoint_stops_abandoned_workers(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "0.2")
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_RETRIES", "0")
    progressed = []
    resumed = threading.Event()

    def wedge_then_touch_ctx():
        time.sleep(0.6)
        watchdog.raise_if_cancelled()  # the checkpoint must fire here
        progressed.append(True)
        resumed.set()

    dog = watchdog.DispatchWatchdog()
    with pytest.raises(watchdog.DispatchAbandoned):
        dog.supervised("k", wedge_then_touch_ctx)
    # give the parked worker time to wake and hit the checkpoint
    assert not resumed.wait(timeout=2.0)
    assert not progressed, "abandoned worker ran past the checkpoint"


def test_fault_env_spec_parsing(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_FAULT", "dispatch_hang:3:1, rpc_error")
    faults.reset_for_tests()
    plane = faults.get_fault_plane()
    assert plane._armed["dispatch_hang"]["times"] == 3
    assert plane._armed["dispatch_hang"]["skip"] == 1
    assert plane._armed["rpc_error"]["times"] == 1
    # skip consumes hits before the first shot fires
    assert plane.fire("dispatch_hang") is None
    assert plane.fire("dispatch_hang") is not None


def test_malformed_fault_spec_fails_loudly(monkeypatch):
    """A typo'd injection point (or non-integer field) must die at
    plane construction — a chaos run configured to inject nothing used
    to pass vacuously."""
    for bad in ("bogus_point:2", "dispatch_hang:lots", "dispatch_hang:1:x"):
        monkeypatch.setenv("MYTHRIL_TPU_FAULT", bad)
        faults.reset_for_tests()
        with pytest.raises(faults.FaultSpecError):
            faults.get_fault_plane()
    monkeypatch.delenv("MYTHRIL_TPU_FAULT")
    monkeypatch.setenv("MYTHRIL_TPU_KILL_AT", "no_such_point")
    faults.reset_for_tests()
    with pytest.raises(faults.FaultSpecError):
        faults.get_fault_plane()
    monkeypatch.delenv("MYTHRIL_TPU_KILL_AT")
    faults.reset_for_tests()


def test_shutdown_join_is_bounded(monkeypatch):
    import mythril_tpu.ops.async_dispatch as AD

    monkeypatch.setenv("MYTHRIL_TPU_SHUTDOWN_JOIN_S", "0.2")
    wedged = threading.Thread(target=lambda: time.sleep(10), daemon=True)
    wedged.start()
    dispatcher = AD.get_async_dispatcher()
    monkeypatch.setattr(dispatcher, "_live_thread", wedged)
    began = time.monotonic()
    AD.join_pending_at_exit()
    assert time.monotonic() - began < 2.0, (
        "shutdown join is not bounded by MYTHRIL_TPU_SHUTDOWN_JOIN_S"
    )


def test_jsonv2_report_carries_degradation_telemetry():
    from mythril_tpu.analysis.report import Report

    resilience_stats.reset()
    resilience_stats.watchdog_trips = 2
    resilience_stats.demotions = 1
    import json

    payload = json.loads(Report().as_swc_standard_format())
    meta = payload[0]["meta"]
    assert meta["resilience"] == {"watchdog_trips": 2, "demotions": 1}
    resilience_stats.reset()
    payload = json.loads(Report().as_swc_standard_format())
    assert "resilience" not in payload[0]["meta"]
