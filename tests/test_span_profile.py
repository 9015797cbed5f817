"""The per-analysis span profile (observability/spans.py): self seconds
that add up to the analysis's wall, threads that do and do not count,
the async prefetch credited to the analysis that launched it, spans on
the profiler's host plane, the fuse-skip count that survives a re-arm,
one XLA module name per kernel, and the benchmark's readers of the
record."""

import contextlib
import glob
import io
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from mythril_tpu.observability import spans

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(autouse=True)
def clean_plane(monkeypatch):
    monkeypatch.delenv("MYTHRIL_TPU_TRACE", raising=False)
    spans.reset_for_tests()
    yield
    spans.reset_for_tests()


def _self_sum(record):
    return sum(record["self_s"].values())


# -- the record ------------------------------------------------------------


def test_cli_record_adds_up_to_the_callers_wall(monkeypatch):
    """One ``myth analyze`` in this process: the record's wall is the
    caller's clock around ``cli.main`` within max(0.05 s, 2%), and the
    self seconds of every span, the root's own included, add up to it
    within 1%."""
    from mythril_tpu.interfaces.cli import main
    from mythril_tpu.resilience import checkpoint

    argv = ["myth", "analyze", "-c", "0x6001600101", "--bin-runtime",
            "-t", "1", "--no-onchain-data", "--execution-timeout", "30",
            "-o", "jsonv2"]
    monkeypatch.setattr(sys, "argv", argv)
    # the drain handlers would outlive this test in the worker process
    monkeypatch.setattr(checkpoint, "install_signal_handlers",
                        lambda: None)
    out = io.StringIO()
    began = time.monotonic()
    with contextlib.redirect_stdout(out):
        main()
    wall = time.monotonic() - began
    assert json.loads(out.getvalue().strip().splitlines()[-1])
    (record,) = spans.analysis_profiles()
    assert record["name"] == "cli.analyze"
    assert abs(record["wall_s"] - wall) <= max(0.05, 0.02 * wall)
    assert _self_sum(record) == pytest.approx(record["wall_s"], rel=0.01)
    assert record["self_s"]["cli.analyze"] < record["wall_s"]
    assert any(name.startswith("svm.") for name in record["self_s"])
    assert record["spans"] > 3


def test_worker_spans_stay_out_and_borrowed_spans_count():
    """A thread of its own (the prefetch worker's kind) adds nothing to
    the analysis; a thread that borrows the waiting thread's span (a
    supervised dispatch) nests under it, and the self seconds still
    add up to the wall."""
    done = threading.Event()

    def stranger():
        with spans.span("worker.own"):
            time.sleep(0.02)
        done.set()

    with spans.analysis("cli.analyze"):
        with spans.span("dispatch.batch_check") as waiting:
            loan = spans.lend()

            def borrower():
                spans.borrow(loan)
                with spans.span("resident.solve"):
                    time.sleep(0.02)

            for target in (stranger, borrower):
                thread = threading.Thread(target=target)
                thread.start()
                thread.join(timeout=30)
                assert not thread.is_alive()
            loan.revoke()
    assert done.is_set()
    (record,) = spans.analysis_profiles()
    self_s = record["self_s"]
    assert "worker.own" not in self_s
    assert self_s["resident.solve"] >= 0.02
    assert self_s["dispatch.batch_check"] == pytest.approx(
        waiting.elapsed_s - self_s["resident.solve"], abs=1e-6)
    assert _self_sum(record) == pytest.approx(record["wall_s"], rel=1e-6)


def test_revoked_loan_counts_nowhere():
    with spans.analysis("cli.analyze"):
        with spans.span("dispatch.round"):
            loan = spans.lend()
        loan.revoke()

        def late():
            spans.borrow(loan)
            with spans.span("late.worker"):
                pass

        thread = threading.Thread(target=late)
        thread.start()
        thread.join(timeout=30)
    (record,) = spans.analysis_profiles()
    assert "late.worker" not in record["self_s"]


def test_watchdog_dispatch_and_compile_count_in_the_analysis():
    """The supervised dispatch thread borrows the waiting span; a
    compile that jax reports on it is a ``device.compile`` child, so
    the dispatch's own self seconds exclude it."""
    import jax.monitoring

    from mythril_tpu.resilience import watchdog

    watchdog.reset_for_tests()
    watchdog.compile_clock()
    event = "/jax/core/compile/backend_compile_duration"

    def thunk():
        with spans.span("resident.solve"):
            jax.monitoring.record_scalar(event, time.time())
            time.sleep(0.05)
            jax.monitoring.record_event_duration_secs(event, 0.05)
            with spans.wait("resident.fetch"):
                time.sleep(0.01)
        return 7

    with spans.analysis("cli.analyze"):
        with spans.span("dispatch.batch_check"):
            assert watchdog.get_watchdog().run("span-test", thunk) == 7
    (record,) = spans.analysis_profiles()
    assert record["compile_s"] >= 0.05
    assert record["self_s"]["resident.solve"] < 0.05
    assert record["wait_s"]["resident.fetch"] >= 0.01
    assert _self_sum(record) == pytest.approx(record["wall_s"], rel=1e-6)
    watchdog.reset_for_tests()


def test_prefetch_is_credited_to_the_launching_analysis():
    """The worker's time from launch to the kernel's completion lands
    in the record of the analysis that launched it, though the kernel
    ends after that analysis closed; the worker's own spans do not."""
    from mythril_tpu.ops.async_dispatch import AsyncDispatcher

    release = threading.Event()

    class Kernel:
        def block_until_ready(self):
            release.wait(timeout=30)

        def __array__(self, dtype=None, copy=None):
            return np.ones(1, np.int32)

    class Backend:
        def prepare_gather(self, ctx, sets):
            return lambda: iter([{"status": Kernel(),
                                  "assign": np.zeros((1, 2), np.int8),
                                  "last": True}])

    class Ctx:
        generation = 0

    dispatcher = AsyncDispatcher()
    with spans.analysis("cli.analyze"):
        assert dispatcher.launch(Backend(), Ctx(), [[1]], [[]], [[]])
    with spans.analysis("cli.analyze"):
        pass
    time.sleep(0.05)
    release.set()
    dispatcher._live_thread.join(timeout=30)
    assert not dispatcher._live_thread.is_alive()
    first, second = spans.analysis_profiles()
    assert first["prefetch_inflight_s"] >= 0.05
    assert first["prefetch_inflight_s"] > first["wall_s"]
    assert second["prefetch_inflight_s"] == 0.0
    for record in (first, second):
        assert not any(name.startswith("prefetch.")
                       for name in record["self_s"])


def test_wait_hook_runs_as_each_device_wait_opens(monkeypatch):
    """The hook the async prefetch sets runs on the waiting thread as a
    ``device.wait`` span opens, tracing off, and for no other span."""
    monkeypatch.setenv("MYTHRIL_TPU_TRACE", "0")
    calls = []
    monkeypatch.setattr(spans, "_wait_hook", None)
    spans.set_wait_hook(lambda: calls.append(threading.get_ident()))
    with spans.span("dispatch.batch_check"):
        assert calls == []
        with spans.wait("replay.upload"):
            assert calls == [threading.get_ident()]
    spans.set_wait_hook(None)
    with spans.wait("replay.fetch"):
        pass
    assert len(calls) == 1


def test_prefetch_counters_land_at_the_launch(monkeypatch):
    """``device_dispatch_calls`` and the cone upload's ``h2d_bytes``
    count when the runner is prepared, on the launching thread, not
    when the worker gets to it."""
    from mythril_tpu.ops import batched_sat as BS

    backend = BS.BatchedSatBackend()
    monkeypatch.setattr(backend, "_gather_eligibility",
                        lambda ctx: ("size_bailouts", 4))
    rows = np.zeros((16, BS.MAX_CLAUSE_WIDTH), np.int32)
    monkeypatch.setattr(
        backend, "_build_cone_batch",
        lambda ctx, sets: (rows, np.zeros((1, 5), np.int8),
                           np.arange(1, 5), ()))

    class Ctx:
        class solver:
            num_vars = 4

    BS.dispatch_stats.reset()
    runner = backend.prepare_gather(Ctx(), [[1]])
    assert runner is not None
    assert BS.dispatch_stats.device_dispatch_calls == 1
    assert BS.dispatch_stats.h2d_bytes == rows.nbytes


def test_record_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(spans, "PROFILE_CAP", 4)
    spans.reset_for_tests()
    for i in range(6):
        with spans.analysis(f"a{i}"):
            pass
    names = [r["name"] for r in spans.analysis_profiles()]
    assert names == ["a2", "a3", "a4", "a5"]
    assert spans.profile_counts()["analyses"] == 6


def test_record_goes_out_through_the_exporters(tmp_path):
    from mythril_tpu.observability import metrics

    metrics.reset_for_tests()
    tracer = spans.get_tracer()
    tracer.enable()
    with spans.analysis("cli.analyze"):
        with spans.wait("replay.fetch"):
            time.sleep(0.001)
    payload = json.load(open(tracer.export_chrome(str(tmp_path / "t.json"))))
    (record,) = payload["otherData"]["analyses"]
    assert record["wait_s"]["replay.fetch"] >= 0.001
    text = metrics.get_registry().render()
    assert 'mythril_tpu_span_self_seconds_total{span="device.wait"}' in text
    assert "mythril_tpu_analysis_records_total 1" in text
    metrics.reset_for_tests()


# -- the profiler's clock --------------------------------------------------


def test_spans_sit_on_the_profiler_host_plane(tmp_path):
    """Under ``jax.profiler`` every span opens a TraceMe: cli.analyze
    and svm.round are on the host plane of the .xplane.pb, svm.round
    inside cli.analyze."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with spans.analysis("cli.analyze"):
            with spans.span("svm.round"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name in ("cli.analyze", "svm.round"):
                    found[event.name] = (event.start_ns,
                                         event.start_ns + event.duration_ns)
    assert set(found) == {"cli.analyze", "svm.round"}
    outer, inner = found["cli.analyze"], found["svm.round"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


# -- the fuse-skip count ---------------------------------------------------


def test_fuse_skips_survive_a_rearm(monkeypatch):
    """Trip the adaptive fuse, re-arm it with a deciding retry, trip it
    again: ``dispatch_stats.fuse_skips`` and the analysis record equal
    the sum of the rises the benchmark's FuseWatch adds up, while the
    backend's own count restarted at the re-arm."""
    monkeypatch.setenv("MYTHRIL_TPU_PALLAS", "off")
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    # the static funnel: the autopilot would route these repeated
    # zero-yield lane shapes straight to the tail after two dozen lanes
    monkeypatch.setenv("MYTHRIL_TPU_AUTOPILOT", "0")
    monkeypatch.syspath_prepend(BENCH)
    from yardstick.myth import FuseWatch

    from mythril_tpu.observability import ledger
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.smt import symbol_factory
    from mythril_tpu.smt.solver import get_blast_context, reset_blast_context
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(args, "device_min_lanes", 2)
    monkeypatch.setattr(args, "word_probing", False)
    monkeypatch.setattr(args, "device_force_dispatch", True)
    monkeypatch.setattr(args, "device_coalesce", False)
    mode = {"deciding": False}

    def fake_check(self, ctx, sets, search=True):
        self.device_engaged = True
        self.last_assignments = np.zeros(
            (len(sets), ctx.solver.num_vars + 1), np.int8)
        return [False if mode["deciding"] else None] * len(sets)

    monkeypatch.setattr(BS.BatchedSatBackend, "check_assumption_sets",
                        fake_check)
    reset_blast_context()
    ledger.reset_for_tests()
    ctx = get_blast_context()
    backend = BS.get_backend()
    BS.dispatch_stats.reset()
    watch = FuseWatch()
    watch.start()
    x = symbol_factory.BitVecSym("fuse_count_x", 16)
    sets = [[x == 1], [x == 2]]
    try:
        with spans.analysis("cli.analyze"):
            for _ in range(BS.FUTILE_DISPATCH_FUSE):
                BS.batch_check_states(sets)
            assert backend.fused_generation == ctx.generation
            mode["deciding"] = True
            for _ in range(BS.FUSE_RETRY_PERIOD):  # the last one retries
                BS.batch_check_states(sets)
            assert backend.fused_generation != ctx.generation  # re-armed
            assert backend.fused_skips == 0
            # the host answers repeated lanes from its memos after the
            # retry: fresh lanes for every round from here
            mode["deciding"] = False
            for i in range(BS.FUTILE_DISPATCH_FUSE + 3):
                y = symbol_factory.BitVecSym(f"fuse_count_y{i}", 16)
                BS.batch_check_states([[y == 1], [y == 2]])
                if i == BS.FUTILE_DISPATCH_FUSE - 1:
                    assert backend.fused_generation == ctx.generation
    finally:
        ledger.remove_batch_observer(watch._on_batch)
        reset_blast_context()
    (record,) = spans.analysis_profiles()
    expected = BS.FUSE_RETRY_PERIOD - 1 + 3  # the retry dispatched
    assert watch.skips == expected
    assert BS.dispatch_stats.fuse_skips == expected
    assert record["fuse_skips"] == expected
    assert backend.fused_skips == 3


# -- one module name per kernel --------------------------------------------


def _resident(num_vars, B, C):
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.ops import resident as RK
    from mythril_tpu.ops.frontier import frontier_deg

    V1 = num_vars + 1
    state = RK.resident_state0(np.ones((B, V1), np.int8), B,
                               BS.GATHER_DECISIONS,
                               width=BS.MAX_CLAUSE_WIDTH)
    args = [np.zeros((C, BS.MAX_CLAUSE_WIDTH), np.int32),
            np.zeros((V1, frontier_deg()), np.int32)]
    args += [state[k] for k in RK.RESIDENT_STATE_FIELDS]
    return RK.make_resident_step(num_vars, BS.GATHER_DECISIONS), args


def _frontier(num_vars, B, C):
    from mythril_tpu.ops import batched_sat as BS
    from mythril_tpu.ops import frontier as FR

    V1 = num_vars + 1
    state = FR.frontier_state0(np.ones((B, V1), np.int8), B,
                               BS.GATHER_DECISIONS,
                               width=BS.MAX_CLAUSE_WIDTH)
    args = [np.zeros((C, BS.MAX_CLAUSE_WIDTH), np.int32),
            np.zeros((V1, FR.frontier_deg()), np.int32)]
    args += [state[k] for k in FR.FRONTIER_STATE_FIELDS]
    return FR.make_frontier_round_step(num_vars, 8,
                                       BS.GATHER_DECISIONS), args


def _dense(num_vars, B, C):
    import jax.numpy as jnp

    from mythril_tpu.ops import pallas_prop as PP

    V = num_vars + 1
    planes = [np.zeros((B, C, V), jnp.bfloat16),
              np.zeros((B, C, V), jnp.bfloat16),
              np.zeros((B, C), np.float32)]
    state = PP._dpll_state0(np.zeros((B, V), np.float32), 4, B)
    return PP.make_batched_rounds(C, V, B, 8, max_decisions=4), (
        planes + state)


@pytest.mark.parametrize("build, module", [
    (_resident, "jit_rounds"),
    (_frontier, "jit_frontier_rounds"),
    (_dense, "jit_dense_rounds"),
])
def test_each_kernel_lowers_to_its_own_module(build, module):
    fn, args = build(15, 4, 32)
    text = fn.lower(*args).as_text()
    first = next(line for line in text.splitlines()
                 if line.startswith("module @"))
    assert first.split()[1] == f"@{module}"


# -- the benchmark's readers of the record ---------------------------------


READERS = ("svm_self_s_per_analysis", "host_wait_s_per_analysis",
           "prefetch_inflight_s_per_analysis", "unspanned_s_per_analysis",
           "setup_compile_s", "prefetch_preempted_per_analysis",
           "resident_iterations_per_analysis")


def _record(wall, **fields):
    record = {"name": "cli.analyze", "wall_s": wall, "compile_s": 0.0,
              "prefetch_inflight_s": 0.0, "prefetch_preempted": 0,
              "wait_s": {}, "fuse_skips": 0, "resident_iterations": 0,
              "self_s": {"cli.analyze": 0.01, "svm.round": 0.5,
                         "svm.transaction": 0.1, "device.wait": 0.2}}
    record.update(fields)
    return record


class _Run:
    def __init__(self, walls):
        self.rows = [{"wall_s": wall} for wall in walls]


@pytest.fixture
def readers(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    from yardstick import cells

    return {name: cells.load("metrics", name).read for name in READERS}


@pytest.fixture
def records(monkeypatch):
    from mythril_tpu import observability

    held = []
    monkeypatch.setattr(observability, "analysis_profiles",
                        lambda: list(held))
    return held


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_on_a_wall_mismatch(readers, records, name):
    records += [_record(40.0, compile_s=30.0), _record(1.0),
                _record(2.5)]
    assert readers[name](_Run([1.0, 2.5])) is not None
    records[-1] = _record(2.5 + 0.06)  # past max(0.05 s, 2%)
    assert readers[name](_Run([1.0, 2.5])) is None
    records[-1] = _record(2.5 + 0.04)  # within it
    assert readers[name](_Run([1.0, 2.5])) is not None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_with_fewer_records(readers, records, name):
    records += [_record(1.0)]
    assert readers[name](_Run([1.0, 1.0])) is None


def test_readers_read_the_window_and_the_warmup(readers, records):
    records += [_record(40.0, compile_s=30.0),
                _record(1.0, prefetch_inflight_s=0.9, prefetch_preempted=1,
                        resident_iterations=1537),
                _record(3.0, prefetch_inflight_s=2.9, resident_iterations=771,
                        self_s={"cli.analyze": 0.03, "svm.round": 1.0,
                                "device.wait": 1.5})]
    run = _Run([1.0, 3.0])
    assert readers["svm_self_s_per_analysis"](run) == pytest.approx(0.8)
    assert readers["host_wait_s_per_analysis"](run) == pytest.approx(0.85)
    assert readers["prefetch_inflight_s_per_analysis"](run) == (
        pytest.approx(1.9))
    assert readers["unspanned_s_per_analysis"](run) == pytest.approx(0.02)
    assert readers["setup_compile_s"](run) == pytest.approx(30.0)
    assert readers["prefetch_preempted_per_analysis"](run) == (
        pytest.approx(0.5))
    assert readers["resident_iterations_per_analysis"](run) == (
        pytest.approx(1154.0))


def test_readers_find_nothing_in_a_program_without_records(readers,
                                                           monkeypatch):
    from mythril_tpu import observability

    monkeypatch.delattr(observability, "analysis_profiles")
    for read in readers.values():
        assert read(_Run([1.0])) is None
