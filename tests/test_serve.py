"""Serve-plane tests: the persistent analysis daemon's failure story.

One live in-process server (module-scoped: engine thread + HTTP
listener on an ephemeral port) carries the end-to-end cases — smoke,
input hardening, deadline drain, request isolation, degraded mode —
while admission, breakers, budgets, and the coalescer's cross-request
scope are pinned at unit level.  Everything here is tier-1 (CPU,
small assembler contracts, sub-second deadlines).
"""

import json
import os
import time
import urllib.error
import urllib.request

import pytest

from mythril_tpu.serve.admission import AdmissionQueue, CircuitBreaker
from mythril_tpu.serve.config import (
    ServeConfig,
    ServeConfigError,
    current_rss_mb,
)
from mythril_tpu.serve.protocol import (
    AnalyzeRequest,
    RequestError,
    parse_analyze_request,
)

pytestmark = pytest.mark.serve


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


def _clean_process_state():
    from mythril_tpu.ops.async_dispatch import get_async_dispatcher
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.ops.coalesce import (
        reset_coalescer, set_request_scope, set_serve_mode,
    )
    from mythril_tpu.resilience import budget, faults, watchdog
    from mythril_tpu.resilience.checkpoint import reset_for_tests
    from mythril_tpu.smt.solver import reset_blast_context

    budget.reset_for_tests()
    faults.reset_for_tests()
    watchdog.reset_for_tests()
    reset_for_tests()
    set_serve_mode(False)
    set_request_scope(None)
    reset_coalescer(hard=True)
    get_async_dispatcher().drop()
    dispatch_stats.reset()
    reset_blast_context()


@pytest.fixture(scope="module")
def server():
    """One live daemon for the whole module (breakers tuned fast)."""
    saved = {
        k: os.environ.get(k)
        for k in ("MYTHRIL_TPU_SERVE_BREAKER",
                  "MYTHRIL_TPU_SERVE_BREAKER_COOLDOWN")
    }
    os.environ["MYTHRIL_TPU_SERVE_BREAKER"] = "2"
    os.environ["MYTHRIL_TPU_SERVE_BREAKER_COOLDOWN"] = "0.5"
    _clean_process_state()
    from mythril_tpu.serve import AnalysisServer

    srv = AnalysisServer(ServeConfig.from_env(port=0))
    srv.start()
    yield srv
    srv.drain_and_stop("tests done")
    for key, value in saved.items():
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = value
    _clean_process_state()


def _post(srv, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/analyze",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, json.loads(resp.read()), resp.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), e.headers


def _get(srv, path):
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=30
        )
        return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _killbilly():
    import bench

    return bench._corpus()[0][1]


# ---------------------------------------------------------------------------
# smoke: start, analyze one contract over HTTP, clean surfaces
# ---------------------------------------------------------------------------


def test_server_smoke_analyze_over_http(server):
    status, body, _ = _post(server, {
        "code": _killbilly(), "name": "killbilly", "tx_count": 1,
        "source": "smoke",
    })
    assert status == 200, body
    assert "106" in body["findings_swc"], body
    assert body["partial"] is False
    assert body["mode"] in ("device", "host-cdcl")
    # a second (warm) request exercises the resident amortization path
    status, body2, _ = _post(server, {
        "code": _killbilly(), "name": "killbilly", "tx_count": 1,
        "source": "smoke",
    })
    assert status == 200
    assert body2["findings_swc"] == body["findings_swc"]


def test_health_ready_metrics_surfaces(server):
    status, raw = _get(server, "/healthz")
    health = json.loads(raw)
    assert status == 200 and health["ok"] is True
    assert health["rss_mb"] > 0

    status, raw = _get(server, "/readyz")
    ready = json.loads(raw)
    assert status == 200 and ready["ready"] is True
    assert ready["mode"] in ("device", "host-cdcl")
    assert set(ready["queue_depths"]) == {"interactive", "batch"}

    status, raw = _get(server, "/metrics")
    text = raw.decode()
    assert status == 200
    assert "mythril_tpu_serve_requests_total" in text
    assert "mythril_tpu_serve_queue_depth_interactive" in text
    assert "mythril_tpu_resilience_watchdog_trips" in text

    status, _ = _get(server, "/nope")
    assert status == 404


# ---------------------------------------------------------------------------
# input hardening: structured 4xx, never a traceback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("payload,code", [
    ({"code": "zz80"}, "bad_bytecode"),
    ({"code": "608"}, "bad_bytecode"),
    ({"code": ""}, "bad_bytecode"),
    ({}, "bad_bytecode"),
    ({"code": "6080", "priority": "urgent"}, "bad_class"),
    ({"code": "6080", "deadline_s": -2}, "bad_deadline"),
    ({"code": "6080", "deadline_s": 1e9}, "bad_deadline"),
    ({"code": "6080", "tx_count": 99}, "bad_tx_count"),
    ({"code": "6080", "tx_count": "two"}, "bad_tx_count"),
    ({"code": "6080", "solc_json": "{not json"}, "bad_solc_json"),
    ({"code": "6080", "solc_json": [1]}, "bad_solc_json"),
    ({"code": "6080", "modules": "Suicide"}, "bad_modules"),
    ({"code": "6080", "source": ""}, "bad_source"),
])
def test_malformed_bodies_are_structured_4xx(server, payload, code):
    status, body, _ = _post(server, payload)
    assert 400 <= status < 500, body
    assert body["error"]["code"] == code, body
    assert "Traceback" not in json.dumps(body)


def test_broken_json_is_400_not_traceback(server):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/analyze",
        data=b"{this is not json",
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=30)
    body = json.loads(exc.value.read())
    assert exc.value.code == 400
    assert body["error"]["code"] == "bad_json"


def test_oversized_body_is_413_with_limit():
    config = ServeConfig(max_body_bytes=64)
    with pytest.raises(RequestError) as exc:
        parse_analyze_request(b"x" * 65, config)
    assert exc.value.status == 413
    assert exc.value.code == "body_too_large"
    assert exc.value.extra["limit_bytes"] == 64


def test_valid_request_parses_with_defaults():
    config = ServeConfig()
    request = parse_analyze_request(
        json.dumps({"code": "0x6080", "deadline_s": 5}).encode(), config
    )
    assert request.code == "6080"          # 0x stripped
    assert request.tx_count == 2
    assert request.priority == "interactive"
    assert request.deadline_s == 5.0


# ---------------------------------------------------------------------------
# config validation at startup (the FaultSpecError pattern)
# ---------------------------------------------------------------------------


def test_malformed_env_knob_dies_at_startup(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_SERVE_MAX_BODY", "a-lot")
    with pytest.raises(ServeConfigError):
        ServeConfig.from_env()


def test_contradictory_deadlines_die_at_startup(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_SERVE_DEADLINE", "120")
    monkeypatch.setenv("MYTHRIL_TPU_SERVE_MAX_DEADLINE", "60")
    with pytest.raises(ServeConfigError):
        ServeConfig.from_env()


def test_negative_queue_depth_dies_at_startup(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_SERVE_QUEUE", "-1")
    with pytest.raises(ServeConfigError):
        ServeConfig.from_env()


# ---------------------------------------------------------------------------
# admission control: bounded queues, watermark, breakers (unit level)
# ---------------------------------------------------------------------------


def _request(source="unit", priority="interactive"):
    return AnalyzeRequest(code="6080", source=source, priority=priority)


def test_queue_full_sheds_with_retry_after():
    queue = AdmissionQueue(ServeConfig(
        queue_cap_interactive=2, queue_cap_batch=1, retry_after_s=7,
    ))
    queue.submit(_request())
    queue.submit(_request())
    with pytest.raises(RequestError) as exc:
        queue.submit(_request())
    assert exc.value.status == 503
    assert exc.value.code == "queue_full"
    assert exc.value.extra["retry_after_s"] == 7
    # the batch class has its own bound: one fits, the next sheds
    queue.submit(_request(priority="batch"))
    with pytest.raises(RequestError):
        queue.submit(_request(priority="batch"))


def test_interactive_class_pops_first():
    queue = AdmissionQueue(ServeConfig())
    queue.submit(_request(source="b", priority="batch"))
    queue.submit(_request(source="i", priority="interactive"))
    assert queue.pop(timeout=0).request.source == "i"
    assert queue.pop(timeout=0).request.source == "b"


def test_rss_watermark_sheds():
    # a 1 MiB watermark is always exceeded by a live python process
    assert current_rss_mb() > 1
    queue = AdmissionQueue(ServeConfig(rss_watermark_mb=1))
    with pytest.raises(RequestError) as exc:
        queue.submit(_request())
    assert exc.value.code == "overloaded_rss"
    assert exc.value.status == 503


def test_draining_queue_sheds_and_returns_pending():
    queue = AdmissionQueue(ServeConfig())
    queue.submit(_request())
    pending = queue.close()
    assert len(pending) == 1
    with pytest.raises(RequestError) as exc:
        queue.submit(_request())
    assert exc.value.code == "draining"
    assert queue.pop(timeout=0) is None  # closed and empty


def test_breaker_opens_after_threshold_and_recovers():
    breaker = CircuitBreaker(threshold=3, cooldown_s=0.2)
    for _ in range(2):
        breaker.record_failure()
    assert breaker.state == "closed" and breaker.allow()
    breaker.record_failure()
    assert breaker.state == "open" and not breaker.allow()
    assert breaker.retry_after_s() >= 1
    time.sleep(0.25)
    assert breaker.state == "half-open"
    assert breaker.allow()        # exactly one half-open probe
    assert not breaker.allow()    # a second concurrent probe is shed
    breaker.record_success()
    assert breaker.state == "closed" and breaker.failures == 0


def test_failed_half_open_probe_reopens():
    breaker = CircuitBreaker(threshold=1, cooldown_s=0.2)
    breaker.record_failure()
    assert breaker.state == "open"
    time.sleep(0.25)
    assert breaker.allow()
    breaker.record_failure()      # probe failed
    assert breaker.state == "open"
    assert not breaker.allow()


def test_queue_breaker_sheds_per_source():
    queue = AdmissionQueue(ServeConfig(
        breaker_threshold=2, breaker_cooldown_s=60.0,
    ))
    for _ in range(2):
        queue.record_outcome("toxic", ok=False)
    with pytest.raises(RequestError) as exc:
        queue.submit(_request(source="toxic"))
    assert exc.value.code == "breaker_open"
    assert exc.value.extra["retry_after_s"] >= 1
    # other sources are untouched
    queue.submit(_request(source="innocent"))
    assert queue.breaker_states() == {"toxic": "open"}


# ---------------------------------------------------------------------------
# deadline budgets (unit + the faults-marked propagation test)
# ---------------------------------------------------------------------------


def test_budget_expiry_flows_into_drain_requested():
    from mythril_tpu.resilience import budget
    from mythril_tpu.resilience.checkpoint import drain_requested
    from mythril_tpu.resilience.telemetry import resilience_stats

    budget.reset_for_tests()
    base = resilience_stats.deadline_expiries
    assert not drain_requested()
    budget.install_budget(60.0)
    assert not drain_requested()      # plenty of budget left
    budget.install_budget(0.0)
    time.sleep(0.01)
    assert drain_requested()
    assert drain_requested()          # stable, and reported only once
    assert resilience_stats.deadline_expiries == base + 1
    budget.clear_budget()
    assert not drain_requested()      # the NEXT request starts clean


def test_expired_budget_does_not_trip_second_signal_path():
    """A first SIGTERM during a budget-expired request must take the
    graceful path (the force-exit branch keys on the signal flag, not
    on drain_requested())."""
    from mythril_tpu.resilience import budget
    from mythril_tpu.resilience import checkpoint

    budget.install_budget(0.0)
    time.sleep(0.01)
    try:
        assert checkpoint.drain_requested()
        assert not checkpoint._drain_event.is_set()
    finally:
        budget.clear_budget()


@pytest.mark.faults
def test_deadline_drains_at_transaction_start_boundary(monkeypatch):
    """The satellite contract: a per-request budget expiring between
    transactions drains at the NEXT transaction's START boundary, the
    report is flagged partial, and the findings are exactly the
    uninterrupted run's prefix (here: identical to a tx_count=1 run of
    the same contract — the storage-armed suicide below only becomes
    reachable at tx 2, so the prefix is observably shorter than the
    full run)."""
    from mythril_tpu.laser.ethereum import transaction as tx_mod
    from mythril_tpu.resilience import budget
    from mythril_tpu.resilience.checkpoint import get_checkpoint_plane
    from mythril_tpu.support.assembler import asm
    from mythril_tpu.support.signatures import selector_of

    _clean_process_state()
    # two-stage kill switch: tx 1 arms storage[0], tx 2's SUICIDE sits
    # behind the armed flag.  Deployed through CREATION code so storage
    # starts concrete-empty (a bytecode-only load gets symbolic
    # storage, which would make the guard reachable in one tx)
    arm_sel = selector_of("arm()")
    kill_sel = selector_of("kill()")
    runtime = asm(f"""
        PUSH 0; CALLDATALOAD; PUSH 0xe0; SHR
        DUP1; PUSH4 {arm_sel}; EQ; PUSH @arm; JUMPI
        DUP1; PUSH4 {kill_sel}; EQ; PUSH @kill; JUMPI
        PUSH 0; PUSH 0; REVERT
      arm:
        JUMPDEST; PUSH 1; PUSH 0; SSTORE; STOP
      kill:
        JUMPDEST; PUSH 0; SLOAD; PUSH @doit; JUMPI
        PUSH 0; PUSH 0; REVERT
      doit:
        JUMPDEST; CALLER; SUICIDE
    """)
    rt_len = len(runtime) // 2
    creation = (
        f"61{rt_len:04x}61000f600039"   # CODECOPY(0, 0x0f, len)
        f"61{rt_len:04x}6000f3{runtime}"  # RETURN(0, len) + payload
    )

    def analyze(tx_count, poison_after_first_tx=False):
        from mythril_tpu.analysis.module.loader import ModuleLoader
        from mythril_tpu.analysis.security import fire_lasers
        from mythril_tpu.analysis.symbolic import SymExecWrapper
        from mythril_tpu.laser.ethereum.time_handler import time_handler
        from mythril_tpu.smt.solver import reset_blast_context
        from mythril_tpu.solidity.evmcontract import EVMContract
        from mythril_tpu.support.model import clear_model_cache

        reset_blast_context()
        clear_model_cache()
        for module in ModuleLoader().get_detection_modules():
            module.reset_module()
            module.cache.clear()
        get_checkpoint_plane().partial = False
        real = tx_mod.execute_message_call
        calls = []

        def instrumented(laser, address):
            result = real(laser, address)
            calls.append(address)
            if poison_after_first_tx and len(calls) == 1:
                # deterministic mid-run expiry: the budget dies the
                # moment transaction 0 completes, so the drain MUST
                # land at transaction 1's start boundary
                budget.install_budget(0.0)
            return result

        monkeypatch.setattr(
            tx_mod, "execute_message_call", instrumented
        )
        time_handler.start_execution(120)
        try:
            sym = SymExecWrapper(
                EVMContract(code=runtime, creation_code=creation,
                            name="armed_kill"),
                address=0x901D12EBE1B195E5AA8748E62BD7734AE19B51F,
                strategy="bfs",
                max_depth=128,
                execution_timeout=120,
                create_timeout=10,
                transaction_count=tx_count,
            )
            issues = fire_lasers(sym)
        finally:
            monkeypatch.setattr(tx_mod, "execute_message_call", real)
            budget.clear_budget()
        return {i.swc_id for i in issues}, sym.laser

    prefix_ref, _ = analyze(tx_count=1)
    full_ref, _ = analyze(tx_count=2)
    assert "106" in full_ref
    assert full_ref - prefix_ref, "need a finding only tx 2 can reach"

    drained, laser = analyze(tx_count=2, poison_after_first_tx=True)
    assert laser.aborted_at_tx == 1        # START boundary of tx 1
    assert get_checkpoint_plane().partial  # report ships partial: true
    assert drained == prefix_ref           # exactly the prefix
    # the expired budget was cleared: a follow-up full run is untouched
    again, _ = analyze(tx_count=2)
    assert again == full_ref


def test_deadline_over_http_partial_then_unaffected(server):
    """End to end: a tiny deadline yields partial: true with
    meta.resilience carrying the expiry; the very next request on the
    same warm server is complete and correct."""
    import bench

    tree = bench.chaos_tree_contract()
    status, body, _ = _post(server, {
        "code": tree, "name": "tree", "tx_count": 2,
        "deadline_s": 0.05, "source": "deadline",
    })
    assert status == 200, body
    assert body["partial"] is True
    assert body["meta"]["resilience"]["partial"] is True
    assert body["meta"]["resilience"]["deadline_expiries"] >= 1

    status, after, _ = _post(server, {
        "code": tree, "name": "tree", "tx_count": 1,
        "deadline_s": 300, "source": "deadline",
    })
    assert status == 200, after
    assert after["partial"] is False
    assert "106" in after["findings_swc"]
    # the partial run's findings are a prefix of the full run's
    assert set(body["findings_swc"]) <= set(after["findings_swc"])


# ---------------------------------------------------------------------------
# request isolation: a poisoned request fails alone
# ---------------------------------------------------------------------------


def test_poisoned_request_fails_alone_with_parity(server):
    from mythril_tpu.resilience import faults

    status, reference, _ = _post(server, {
        "code": _killbilly(), "name": "killbilly", "tx_count": 1,
        "source": "clean",
    })
    assert status == 200

    faults.get_fault_plane().arm("serve_crash", times=1)
    try:
        status, body, _ = _post(server, {
            "code": _killbilly(), "name": "killbilly", "tx_count": 1,
            "source": "poison-iso",
        })
    finally:
        faults.reset_for_tests()
    assert status == 500
    assert body["error"]["code"] == "analysis_failed"
    assert "Traceback" not in json.dumps(body)

    # the server stays ready, and the next request's findings match
    status, raw = _get(server, "/readyz")
    assert status == 200 and json.loads(raw)["ready"] is True
    status, after, _ = _post(server, {
        "code": _killbilly(), "name": "killbilly", "tx_count": 1,
        "source": "clean",
    })
    assert status == 200
    assert after["findings_swc"] == reference["findings_swc"]


def test_repeated_poison_trips_breaker_then_recovers(server):
    """threshold=2, cooldown=0.5s (module fixture env): two crashed
    requests from one source open its breaker; a third sheds instantly
    with Retry-After; after the cooldown a clean probe closes it."""
    from mythril_tpu.resilience import faults

    payload = {
        "code": _killbilly(), "name": "killbilly", "tx_count": 1,
        "source": "toxic-http",
    }
    faults.get_fault_plane().arm("serve_crash", times=2)
    try:
        for _ in range(2):
            status, body, _ = _post(server, payload)
            assert status == 500, body
        status, body, headers = _post(server, payload)
        assert status == 503
        assert body["error"]["code"] == "breaker_open"
        assert int(headers["Retry-After"]) >= 1
    finally:
        faults.reset_for_tests()
    time.sleep(0.6)  # past the cooldown: half-open admits one probe
    status, body, _ = _post(server, payload)
    assert status == 200, body
    status, raw = _get(server, "/readyz")
    assert json.loads(raw)["breakers"].get("toxic-http") == "closed"


# ---------------------------------------------------------------------------
# degraded host-CDCL mode
# ---------------------------------------------------------------------------


def test_device_demotion_degrades_but_serves(server):
    from mythril_tpu.ops import device_health
    from mythril_tpu.resilience import watchdog

    try:
        # the ladder's last rung, fed a dispatch error that says the
        # device is gone, demotes the whole process in-process
        with pytest.raises(watchdog.DispatchAbandoned) as exc_info:
            watchdog.DispatchWatchdog().give_up(
                "serve-test", RuntimeError("UNAVAILABLE: device lost")
            )
        assert exc_info.value.process_demoted is True
        status, raw = _get(server, "/readyz")
        ready = json.loads(raw)
        assert status == 200          # degraded is NOT unready
        assert ready["ready"] is True
        assert ready["degraded"] is True
        assert ready["mode"] == "host-cdcl"
        status, body, _ = _post(server, {
            "code": _killbilly(), "name": "killbilly", "tx_count": 1,
            "source": "degraded",
        })
        assert status == 200
        assert "106" in body["findings_swc"]
        assert body["mode"] == "host-cdcl"
    finally:
        device_health.reset_for_tests()


# ---------------------------------------------------------------------------
# graceful drain flushes artifacts (CLI and serve share the seam)
# ---------------------------------------------------------------------------


def test_drain_flushes_trace_and_metrics_artifacts(tmp_path):
    from mythril_tpu.observability import get_tracer
    from mythril_tpu.resilience import checkpoint
    from mythril_tpu.support.support_args import args

    _clean_process_state()
    trace_out = str(tmp_path / "drain.trace.json")
    metrics_out = str(tmp_path / "drain.metrics.prom")
    saved = (args.trace_out, args.metrics_out)
    args.trace_out, args.metrics_out = trace_out, metrics_out
    tracer = get_tracer()
    tracer.enable()
    try:
        checkpoint.request_drain("artifact-flush-test")
        # the artifacts landed AT DRAIN TIME — a later hard kill can no
        # longer lose the timeline
        assert os.path.exists(trace_out)
        assert os.path.exists(metrics_out)
        trace = json.load(open(trace_out))
        assert isinstance(trace.get("traceEvents"), list)
        assert "mythril_tpu_resilience_watchdog_trips" in open(
            metrics_out
        ).read()
    finally:
        tracer.disable()
        args.trace_out, args.metrics_out = saved
        _clean_process_state()


# ---------------------------------------------------------------------------
# cross-request coalescer scope
# ---------------------------------------------------------------------------


def test_coalescer_scope_stamp_and_purge():
    from mythril_tpu.ops import coalesce

    _clean_process_state()
    coalesce.set_serve_mode(True)
    try:
        co = coalesce.get_coalescer()
        coalesce.set_request_scope("req-a")
        co.queue[(1,)] = coalesce.QueuedLane(
            (1,), [1], None, None, "req-a"
        )
        coalesce.set_request_scope("req-b")
        co.queue[(2,)] = coalesce.QueuedLane(
            (2,), [2], None, None, "req-b"
        )
        assert coalesce.purge_scope("req-a") == 1
        assert list(co.queue) == [(2,)]
        # soft (per-request telemetry) reset keeps the queue in serve
        # mode; a hard reset (decontamination) drops it
        co.dispatched = 3
        coalesce.reset_coalescer()
        assert list(co.queue) == [(2,)]
        assert co.dispatched == 3
        coalesce.reset_coalescer(hard=True)
        assert not co.queue and co.dispatched == 0
    finally:
        _clean_process_state()


def test_coalescer_cli_mode_reset_still_drops_everything():
    from mythril_tpu.ops import coalesce

    _clean_process_state()
    co = coalesce.get_coalescer()
    co.queue[(9,)] = coalesce.QueuedLane((9,), [9], None, None)
    co.dispatched = 2
    coalesce.reset_coalescer()   # serve mode off: full reset
    assert not co.queue and co.dispatched == 0


def test_report_cache_hit_mints_trace_and_counts(tmp_path, monkeypatch):
    """An admission-edge cache hit is still a served request: it must
    carry a trace_id (echoed when the caller sent one, minted when
    not — stored bodies predate the engine's trace stamp) and count on
    ``mythril_tpu_serve_cache_hits`` so watch-stream dedup is visible
    from ``/debug/watch`` and ``myth top``."""
    from mythril_tpu.observability import metrics as metrics_mod
    from mythril_tpu.persist import plane as plane_mod

    monkeypatch.setenv("MYTHRIL_TPU_PERSIST_DIR", str(tmp_path))
    monkeypatch.setenv("MYTHRIL_TPU_PERSIST_FLUSH_S", "0")
    plane_mod.reset_for_tests()
    metrics_mod.reset_for_tests()
    try:
        plane = plane_mod.get_knowledge_plane()
        code = "6001600101"
        plane.report_cache_put(
            plane_mod.code_digest(code), 2, 128, None,
            {"findings_swc": ["106"], "partial": False},
        )
        queue = AdmissionQueue(ServeConfig())
        hit = queue.cached_response(AnalyzeRequest(code=code))
        assert hit["cached"] is True and hit["findings_swc"] == ["106"]
        assert hit["trace_id"], "cache hit minted no trace_id"
        echoed = queue.cached_response(
            AnalyzeRequest(code=code, trace_id="tr-echo")
        )
        assert echoed["trace_id"] == "tr-echo"
        assert queue._m_cache_hits.value == 2
        # a miss neither counts nor invents a body
        assert queue.cached_response(
            AnalyzeRequest(code="6002600201")
        ) is None
        assert queue._m_cache_hits.value == 2
    finally:
        plane_mod.reset_for_tests()
        metrics_mod.reset_for_tests()
