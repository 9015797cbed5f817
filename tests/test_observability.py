"""Observability-plane tests: span nesting/thread-safety, Perfetto
JSON schema validity, the metrics registry (and the exactly-once
contract for the absorbed telemetry counters), the flight recorder's
dump-on-trip via a ``MYTHRIL_TPU_FAULT`` injection, the disabled-path
overhead guard, and the CLI/report surface (``--trace-out`` /
``--metrics-out`` / ``meta.observability``)."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from mythril_tpu.observability import flight, metrics, spans

pytestmark = pytest.mark.obs

MYTH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "myth"
)


@pytest.fixture(autouse=True)
def clean_plane(monkeypatch):
    """Fresh tracer/registry/recorder per test; the telemetry shim
    re-creates its counters in the new registry on first touch."""
    monkeypatch.delenv("MYTHRIL_TPU_TRACE", raising=False)
    spans.reset_for_tests()
    metrics.reset_for_tests()
    flight.reset_for_tests()
    yield
    spans.reset_for_tests()
    metrics.reset_for_tests()
    flight.reset_for_tests()


# -- spans ------------------------------------------------------------------


def test_span_nesting_records_parent_and_totals():
    tracer = spans.get_tracer()
    assert tracer.enable()
    with spans.span("outer"):
        with spans.span("inner"):
            time.sleep(0.01)
    events = {e["name"]: e for e in tracer.events()}
    assert set(events) == {"outer", "inner"}
    assert events["inner"]["args"]["parent"] == "outer"
    assert "parent" not in events["outer"].get("args", {})
    # ts/dur containment: the inner span lies inside the outer one
    outer, inner = events["outer"], events["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    totals = tracer.totals_snapshot()
    assert totals["inner"] >= 0.01
    assert totals["outer"] >= totals["inner"]


def test_span_exception_recorded_and_stack_unwound():
    tracer = spans.get_tracer()
    tracer.enable()
    with pytest.raises(ValueError):
        with spans.span("exploder"):
            raise ValueError("boom")
    (event,) = tracer.events()
    assert event["args"]["error"] == "ValueError"
    # the thread-local stack unwound: a following span has no parent
    with spans.span("after"):
        pass
    after = [e for e in tracer.events() if e["name"] == "after"][0]
    assert "parent" not in after.get("args", {})


def test_span_thread_safety():
    tracer = spans.get_tracer()
    tracer.enable()
    threads, per_thread = 8, 200
    barrier = threading.Barrier(threads)

    def work():
        barrier.wait()  # overlap all workers: distinct thread idents
        for _ in range(per_thread):
            with spans.span("worker.outer"):
                with spans.span("worker.inner"):
                    pass

    pool = [threading.Thread(target=work) for _ in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    assert tracer.span_count == threads * per_thread * 2
    events = tracer.events()
    assert len(events) == threads * per_thread * 2
    assert len({e["tid"] for e in events}) == threads
    # nesting stayed per-thread: every inner's parent is the outer
    for e in events:
        if e["name"] == "worker.inner":
            assert e["args"]["parent"] == "worker.outer"


def test_chrome_trace_export_schema(tmp_path):
    tracer = spans.get_tracer()
    tracer.enable()
    with spans.span("a", cat="pipeline", detail=3):
        spans.instant("tick", cat="event", why="test")
    path = tracer.export_chrome(str(tmp_path / "trace.json"))
    payload = json.load(open(path))
    assert isinstance(payload["traceEvents"], list)
    phases = set()
    for event in payload["traceEvents"]:
        assert isinstance(event["name"], str)
        assert event["ph"] in ("X", "i")
        assert isinstance(event["ts"], (int, float))
        assert isinstance(event["pid"], int)
        assert isinstance(event["tid"], int)
        if event["ph"] == "X":
            assert isinstance(event["dur"], (int, float))
        phases.add(event["ph"])
    assert phases == {"X", "i"}
    assert payload["otherData"]["span_events"] == 1
    assert payload["otherData"]["instant_events"] == 1


def test_trace_buffer_cap_drops_but_keeps_totals(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_TRACE_CAP", "1024")
    spans.reset_for_tests()
    tracer = spans.get_tracer()
    tracer.enable()
    for _ in range(1500):
        with spans.span("flood"):
            pass
    assert len(tracer.events()) == 1024
    assert tracer.dropped == 1500 - 1024
    assert tracer.span_count == 1500
    assert tracer.counts_snapshot()["flood"] == 1500


def test_phase_totals_mapping():
    """The analysis record keeps self seconds by span name, tracing off:
    an enclosing layer (svm.transaction) holds only the time none of
    its children cover, so every second counts once and the record's
    self seconds add up to its wall."""
    assert not spans.get_tracer().enabled
    leaves = ("cone.build", "upload.pool", "dispatch.round",
              "pallas.round", "cdcl.solve")
    with spans.analysis("cli.analyze"):
        with spans.span("svm.transaction") as enclosing:
            for name in leaves:
                with spans.span(name):
                    time.sleep(0.002)
    (record,) = spans.analysis_profiles()
    self_s = record["self_s"]
    for name in leaves:
        assert self_s[name] >= 0.002
    assert self_s["svm.transaction"] == pytest.approx(
        enclosing.elapsed_s - sum(self_s[n] for n in leaves), abs=1e-6)
    assert self_s["svm.transaction"] < 0.002
    assert sum(self_s.values()) == pytest.approx(record["wall_s"],
                                                 rel=1e-9)
    assert record["spans"] == len(leaves) + 2
    assert spans.get_tracer().span_count == 0  # no events recorded


def test_span_sink_feeds_stats_with_tracing_off():
    class Bag:
        device_s = 0.0

    bag = Bag()
    assert not spans.get_tracer().enabled
    with spans.span("dispatch.batch_check",
                    sink=(bag, "device_s")) as sp:
        time.sleep(0.01)
    assert bag.device_s >= 0.01
    assert sp.elapsed_s >= 0.01
    assert spans.get_tracer().span_count == 0  # nothing recorded


def test_device_dispatch_span_layers():
    """The accelerator layers land on the timeline: a pool upload and
    the ladder's budgeted rounds produce upload.* / dispatch.round
    spans, and the analysis record counts their self seconds — the
    rounds run on supervised worker threads, which borrow the waiting
    thread's span — with the uploads and fetches as device waits (the
    CPU jax backend runs the same jitted kernels as the TPU)."""
    from mythril_tpu.ops.batched_sat import (
        BatchedSatBackend, dispatch_stats,
    )
    from mythril_tpu.smt import terms as T
    from mythril_tpu.smt.bitblast import BlastContext

    dispatch_stats.reset()
    tracer = spans.get_tracer()
    tracer.enable()
    ctx = BlastContext()
    lits = [
        ctx.blast_lit(T.eq(T.var(f"ox{i}", 8), T.const(17 * i + 3, 8)))
        for i in range(4)
    ]
    backend = BatchedSatBackend()
    with spans.analysis("cli.analyze"):
        assign = backend._sync_pool_and_assign(
            ctx, [[lit] for lit in lits], ctx.solver.num_vars
        )
        status, _final = backend._solve_gather_ladder(
            "gather", backend.pool.lits, assign
        )
    assert len(status) == len(lits)
    names = set(tracer.totals_snapshot())
    assert "upload.pool" in names
    assert "dispatch.round" in names
    (record,) = spans.analysis_profiles()
    assert record["self_s"]["dispatch.round"] > 0
    assert {"upload.pool", "upload.lanes", "round.fetch"} <= set(
        record["wait_s"])
    assert sum(record["self_s"].values()) == pytest.approx(
        record["wall_s"], rel=1e-6)


# -- disabled-path overhead guard -------------------------------------------


def test_disabled_path_is_allocation_free_and_cheap(monkeypatch):
    """Cost guard of the always-on self time: a span's enter and exit
    with tracing off, inside an analysis record, under 2 us on a CPU
    (best of five batches, so a busy neighbour does not decide it)."""
    monkeypatch.setenv("MYTHRIL_TPU_TRACE", "0")
    spans.reset_for_tests()
    tracer = spans.get_tracer()
    # the kill switch vetoes programmatic enablement
    assert tracer.enable() is False
    assert not tracer.enabled
    spans.instant("never")  # no-op, no error
    assert tracer.instant_count == 0
    n = 20_000
    batches = []
    with spans.analysis("cost.guard"):
        for _ in range(5):
            began = time.perf_counter()
            for _ in range(n):
                with spans.span("hot.path"):
                    pass
            batches.append((time.perf_counter() - began) / n)
    per_call = min(batches)
    assert per_call < 2e-6, f"span cost {per_call * 1e6:.2f}us"
    (record,) = spans.analysis_profiles()
    assert record["spans"] == 5 * n + 1
    assert tracer.span_count == 0


# -- metrics registry -------------------------------------------------------


def test_registry_counter_gauge_histogram_render():
    registry = metrics.get_registry()
    counter = registry.counter("mythril_tpu_test_hits", "test counter")
    counter.inc()
    counter.inc(2)
    registry.gauge("mythril_tpu_test_depth", "test gauge").set(7)
    histogram = registry.histogram(
        "mythril_tpu_test_latency", "test histogram", buckets=(0.1, 1.0)
    )
    histogram.observe(0.05)
    histogram.observe(5.0)
    text = registry.render()
    assert "# TYPE mythril_tpu_test_hits counter" in text
    assert "mythril_tpu_test_hits 3" in text
    assert "mythril_tpu_test_depth 7" in text
    assert 'mythril_tpu_test_latency_bucket{le="0.1"} 1' in text
    assert 'mythril_tpu_test_latency_bucket{le="+Inf"} 2' in text
    assert "mythril_tpu_test_latency_count 2" in text
    # the same metric object comes back on re-registration
    assert registry.counter("mythril_tpu_test_hits") is counter


def test_registry_dump_covers_every_preexisting_counter_bag(tmp_path):
    """The unified dump absorbs telemetry + DispatchStats + AsyncStats:
    every pre-existing counter appears, each exactly once."""
    from mythril_tpu.ops.async_dispatch import async_stats
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.resilience.telemetry import _FIELDS, resilience_stats

    resilience_stats.reset()
    path = metrics.get_registry().dump(str(tmp_path / "m.prom"))
    text = open(path).read()
    lines = text.splitlines()
    for field in _FIELDS:
        name = f"mythril_tpu_resilience_{field}"
        assert sum(1 for l in lines if l.startswith(name + " ")) == 1, name
    for field, value in dispatch_stats.__dict__.items():
        if isinstance(value, (int, float, bool)):
            name = f"mythril_tpu_dispatch_{field}"
            assert sum(
                1 for l in lines if l.startswith(name + " ")
            ) == 1, name
    for field in async_stats.as_dict():
        name = f"mythril_tpu_async_{field}"
        assert sum(1 for l in lines if l.startswith(name + " ")) == 1, name
    assert "mythril_tpu_trace_span_events" in text


def test_telemetry_shim_is_the_single_source_of_truth():
    """resilience_stats attribute traffic lands in registry counters;
    bench rows (DispatchStats.as_dict) and the Prometheus dump read the
    SAME cell — counted exactly once end-to-end."""
    from mythril_tpu.ops.batched_sat import DispatchStats
    from mythril_tpu.resilience.telemetry import resilience_stats

    resilience_stats.reset()
    resilience_stats.watchdog_trips += 3
    resilience_stats.checkpoint_s += 0.25
    registry = metrics.get_registry()
    assert registry.counter(
        "mythril_tpu_resilience_watchdog_trips"
    ).value == 3
    row = DispatchStats().as_dict()
    assert row["watchdog_trips"] == 3
    assert row["checkpoint_s"] == 0.25
    text = registry.render()
    assert sum(
        1 for l in text.splitlines()
        if l.startswith("mythril_tpu_resilience_watchdog_trips ")
    ) == 1
    # the DispatchStats mirror must NOT re-emit the resilience fields
    assert "mythril_tpu_dispatch_watchdog_trips" not in text
    # restore path (checkpoint resume) still works through the shim
    assert hasattr(resilience_stats, "watchdog_trips")
    assert not hasattr(resilience_stats, "not_a_counter")
    resilience_stats.watchdog_trips = 11
    assert resilience_stats.as_dict()["watchdog_trips"] == 11


def test_faults_fired_counted_exactly_once_end_to_end():
    """An injected dispatch fault walks the real retry rung; the
    faults_fired / dispatch_retries counters land in the registry once
    each, with instant events on the timeline."""
    from mythril_tpu.resilience import faults, watchdog
    from mythril_tpu.resilience.telemetry import resilience_stats

    faults.reset_for_tests()
    watchdog.reset_for_tests()
    spans.get_tracer().enable()
    resilience_stats.reset()
    faults.get_fault_plane().arm("dispatch_error", times=1)

    def thunk():
        faults.maybe_fault_dispatch()
        return 42

    try:
        result = watchdog.get_watchdog().run_attempts(
            "obs-test", thunk, retries=2
        )
    finally:
        faults.reset_for_tests()
        watchdog.reset_for_tests()
    assert result == 42
    assert resilience_stats.faults_fired == 1
    assert resilience_stats.dispatch_retries == 1
    text = metrics.get_registry().render()
    assert sum(
        1 for l in text.splitlines()
        if l.startswith("mythril_tpu_resilience_faults_fired ")
    ) == 1
    fired = [e for e in spans.get_tracer().events()
             if e["name"] == "fault.fired"]
    assert fired and fired[0]["args"]["point"] == "dispatch_error"


# -- flight recorder --------------------------------------------------------


def test_flight_ring_is_bounded_and_dumps_perfetto_json(tmp_path):
    tracer = spans.get_tracer()
    tracer.enable()
    recorder = flight.get_flight_recorder()
    recorder.configure(str(tmp_path))
    for i in range(1000):
        with spans.span("ring.filler", i=i):
            pass
    assert len(recorder) <= 512 + 1
    path = recorder.dump("unit_test")
    assert path and os.path.dirname(path) == str(tmp_path)
    payload = json.load(open(path))
    assert payload["otherData"]["reason"] == "unit_test"
    names = {e["name"] for e in payload["traceEvents"]}
    assert names == {"ring.filler"}
    # the ring holds the most RECENT events
    last = payload["traceEvents"][-1]
    assert last["args"]["i"] == 999
    assert recorder.dumps_written == 1


def test_flight_dump_is_noop_when_nothing_buffered(tmp_path):
    recorder = flight.get_flight_recorder()
    recorder.configure(str(tmp_path))
    assert recorder.dump("nothing") is None
    assert os.listdir(str(tmp_path)) == []


def test_flight_dump_on_watchdog_trip_via_fault_injection(
    tmp_path, monkeypatch
):
    """A MYTHRIL_TPU_FAULT dispatch hang trips the watchdog deadline;
    the trip must dump the flight ring (with the spans leading up to
    it) and mark the timeline."""
    monkeypatch.setenv("MYTHRIL_TPU_FAULT", "dispatch_hang")
    monkeypatch.setenv("MYTHRIL_TPU_FAULT_HANG_S", "0.6")
    monkeypatch.setenv("MYTHRIL_TPU_DISPATCH_TIMEOUT", "0.1")
    from mythril_tpu.resilience import faults, watchdog

    faults.reset_for_tests()  # re-read the env schedule
    watchdog.reset_for_tests()
    tracer = spans.get_tracer()
    tracer.enable()
    recorder = flight.get_flight_recorder()
    recorder.configure(str(tmp_path))
    with spans.span("pre.trip.context"):
        pass

    def thunk():
        faults.maybe_fault_dispatch()
        return 1

    try:
        with pytest.raises(watchdog.DispatchFailed):
            watchdog.get_watchdog().run_attempts(
                "obs-hang", thunk, retries=0
            )
    finally:
        faults.reset_for_tests()
        watchdog.reset_for_tests()
    from mythril_tpu.resilience.telemetry import resilience_stats

    assert resilience_stats.watchdog_trips >= 1
    dumps = [n for n in os.listdir(str(tmp_path))
             if "watchdog_trip" in n]
    assert dumps, "no flight dump on watchdog trip"
    payload = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    names = {e["name"] for e in payload["traceEvents"]}
    assert "pre.trip.context" in names
    trips = [e for e in tracer.events() if e["name"] == "watchdog.trip"]
    assert trips and trips[0]["ph"] == "i"


# -- CLI / report surface ---------------------------------------------------


def test_report_meta_observability_section_is_stable():
    from mythril_tpu.analysis.report import Report

    payload = json.loads(Report().as_swc_standard_format())
    section = payload[0]["meta"]["observability"]
    assert set(section) == {
        "enabled", "trace_id", "trace_out", "metrics_out",
        "lane_ledger_out", "span_events", "instant_events",
        "dropped_events", "flight_dumps", "ledger_lanes",
    }
    assert section["enabled"] is False
    assert section["trace_out"] is None


def test_trace_truncation_marker_in_export(tmp_path, monkeypatch):
    """A capped trace must say so on its own timeline: the export
    appends a trace.truncated instant carrying the drop count, and the
    registry's mythril_tpu_trace_dropped_events counter agrees."""
    monkeypatch.setenv("MYTHRIL_TPU_TRACE_CAP", "1024")
    spans.reset_for_tests()
    tracer = spans.get_tracer()
    tracer.enable()
    for _ in range(1200):
        with spans.span("flood"):
            pass
    path = tracer.export_chrome(str(tmp_path / "t.json"))
    payload = json.load(open(path))
    markers = [e for e in payload["traceEvents"]
               if e["name"] == "trace.truncated"]
    assert len(markers) == 1
    assert markers[0]["ph"] == "i"
    assert markers[0]["args"]["dropped_events"] == 1200 - 1024
    text = metrics.get_registry().render()
    assert f"mythril_tpu_trace_dropped_events {1200 - 1024}" in text
    # an uncapped trace carries no marker
    spans.reset_for_tests()
    tracer = spans.get_tracer()
    tracer.enable()
    with spans.span("small"):
        pass
    payload = json.load(open(tracer.export_chrome(
        str(tmp_path / "t2.json")
    )))
    assert not any(e["name"] == "trace.truncated"
                   for e in payload["traceEvents"])


def test_prometheus_escaping_of_labels_and_help():
    """Contract source paths land in label values and HELP text can
    carry anything — backslash/newline/double-quote must be escaped per
    the text-format spec or the whole exposition corrupts."""
    nasty = 'C:\\contracts\n"token".sol'
    assert metrics.escape_label_value(nasty) == (
        r'C:\\contracts\n\"token\".sol'
    )
    registry = metrics.get_registry()
    registry.counter(
        "mythril_tpu_test_nasty_help",
        'line one\nline two with \\ and "quotes"',
    ).inc()
    from mythril_tpu.observability.ledger import get_ledger

    get_ledger().set_origin(contract=nasty)
    batch = get_ledger().begin_batch("batch_check", 1)
    batch.decide(0, "word", "unsat")
    batch.close()
    text = registry.render()
    for line in text.splitlines():
        # the spec-breaking characters never appear raw inside a line
        assert "\r" not in line
        if line.startswith("# HELP mythril_tpu_test_nasty_help"):
            assert "\\n" in line and '\\\\' in line
    assert 'contract="C:\\\\contracts\\n\\"token\\".sol"' in text
    # labeled collector series keep HELP/TYPE on the BASE name
    assert ("# TYPE mythril_tpu_ledger_contract_lanes_total counter"
            in text)
    assert "# TYPE mythril_tpu_ledger_contract_lanes_total{" not in text


def test_flight_dump_filenames_never_collide(tmp_path):
    """Two back-to-back trips must both survive on disk — including
    across a recorder replacement that resets the sequence while the
    dump directory persists (the pid-reuse shape)."""
    tracer = spans.get_tracer()
    tracer.enable()
    recorder = flight.get_flight_recorder()
    recorder.configure(str(tmp_path))
    with spans.span("pre.trip"):
        pass
    first = recorder.dump("trip")
    second = recorder.dump("trip")
    assert first and second and first != second
    assert os.path.exists(first) and os.path.exists(second)
    # a FRESH recorder (seq restarts at 1) in the same dir + pid must
    # bump past the survivors instead of overwriting them
    flight.reset_for_tests()
    fresh = flight.get_flight_recorder()
    fresh.configure(str(tmp_path))
    fresh.record({"name": "later", "ph": "i", "ts": 0.0,
                  "pid": os.getpid(), "tid": 0})
    third = fresh.dump("trip")
    assert third and third not in (first, second)
    assert len([n for n in os.listdir(str(tmp_path))
                if n.endswith("-trip.json")]) == 3


def test_absorb_events_separates_pid_reusing_workers():
    """A respawned fleet worker can reuse a dead worker's OS pid; the
    absorb path must keep the two streams on distinct Perfetto tracks
    (synthetic pids) and re-parent both under the trace id."""
    tracer = spans.get_tracer()
    tracer.enable()
    stream = lambda: [  # noqa: E731 — identical pid on purpose
        {"name": "worker.span", "ph": "X", "ts": 1.0, "dur": 2.0,
         "pid": 4242, "tid": 1}
    ]
    assert tracer.absorb_events(stream(), worker="w1",
                                trace_id="trace-abc") == 1
    assert tracer.absorb_events(stream(), worker="w2",
                                trace_id="trace-abc") == 1
    events = tracer.events()
    spans_abs = [e for e in events if e["name"] == "worker.span"]
    assert len(spans_abs) == 2
    # distinct synthetic pids: the streams cannot merge
    assert spans_abs[0]["pid"] != spans_abs[1]["pid"]
    assert all(e["pid"] != 4242 for e in spans_abs)
    assert all(e["args"]["trace_id"] == "trace-abc" for e in spans_abs)
    # process_name metadata labels each track
    labels = [e for e in events if e.get("ph") == "M"]
    assert {e["args"]["name"] for e in labels} == {
        "fleet-worker w1 [trace trace-abc]",
        "fleet-worker w2 [trace trace-abc]",
    }
    # the SAME worker absorbing twice stays one track
    assert tracer.absorb_events(stream(), worker="w1") == 1
    spans_abs = [e for e in tracer.events()
                 if e["name"] == "worker.span"]
    assert len({e["pid"] for e in spans_abs}) == 2


def test_counter_track_events():
    tracer = spans.get_tracer()
    tracer.enable()
    spans.counter("pool.rows", resident=7, bucket=256)
    (event,) = [e for e in tracer.events() if e["ph"] == "C"]
    assert event["name"] == "pool.rows"
    assert event["args"] == {"resident": 7.0, "bucket": 256.0}
    # disabled: no-op
    tracer.disable()
    spans.counter("pool.rows", resident=9)
    assert len([e for e in tracer.events() if e["ph"] == "C"]) == 1


def test_cli_trace_and_metrics_artifacts(tmp_path):
    """myth analyze --trace-out/--metrics-out writes a Perfetto-loadable
    trace spanning the pipeline layers and a Prometheus dump carrying
    the absorbed telemetry counters; the jsonv2 meta names both."""
    trace_path = tmp_path / "t.json"
    metrics_path = tmp_path / "m.prom"
    ledger_path = tmp_path / "lanes.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, MYTH, "analyze", "-c", "0x6001600101",
         "--bin-runtime", "-t", "1", "--no-onchain-data",
         "--execution-timeout", "30", "-o", "jsonv2",
         "--trace-out", str(trace_path),
         "--metrics-out", str(metrics_path),
         "--lane-ledger-out", str(ledger_path)],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(MYTH), env=env,
    )
    report = json.loads(proc.stdout)
    section = report[0]["meta"]["observability"]
    assert section["enabled"] is True
    assert section["trace_out"] == str(trace_path)
    assert section["span_events"] > 0
    assert section["trace_id"]  # minted at the CLI edge
    assert section["lane_ledger_out"] == str(ledger_path)

    # the lane-ledger artifact is schema-valid and conserves lanes
    # (the acceptance invariant scripts/trace_lint.py enforces)
    sys.path.insert(0, os.path.join(os.path.dirname(MYTH), "scripts"))
    import trace_lint

    ledger_payload = json.load(open(ledger_path))
    assert trace_lint.lint_ledger(ledger_payload) == []
    assert trace_lint.lint_trace(json.load(open(trace_path))) == []

    trace = json.load(open(trace_path))
    names = {e["name"] for e in trace["traceEvents"]}
    # the analysis record rides otherData: its self seconds add up to
    # its wall, the root span's own included
    (record,) = trace["otherData"]["analyses"]
    assert record["name"] == "cli.analyze"
    assert record["trace_id"] == section["trace_id"]
    assert record["self_s"]["cli.analyze"] > 0
    assert sum(record["self_s"].values()) == pytest.approx(
        record["wall_s"], rel=1e-6)
    # the span tree covers the host pipeline layers (device layers
    # need an accelerator; they are pinned by the unit tests above)
    for expected in ("cli.analyze", "analyzer.contract",
                     "svm.transaction", "svm.round", "batch.prune"):
        assert expected in names, f"{expected} missing from {names}"

    prom = open(metrics_path).read()
    from mythril_tpu.resilience.telemetry import _FIELDS

    for field in _FIELDS:
        name = f"mythril_tpu_resilience_{field}"
        assert sum(
            1 for l in prom.splitlines() if l.startswith(name + " ")
        ) == 1, name
    assert "mythril_tpu_dispatch_dispatches" in prom
    assert "mythril_tpu_trace_enabled 1" in prom
    assert "mythril_tpu_analysis_records_total 1" in prom
    assert 'mythril_tpu_span_self_seconds_total{span="svm.round"}' in prom
