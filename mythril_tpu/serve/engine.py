"""The analysis engine: one worker thread that executes admitted
requests serially against the warm, device-resident solver state.

Serial on purpose — device dispatch is a single stream, and the entire
amortization story of the daemon (JAX compile cache, resident clause
pool, cone memo, warm-start models, solver memo channels) lives on ONE
blast context that requests share.  What is per-request is everything
that must not leak between callers:

- **Telemetry scope** — dispatch/resilience counters, solver
  statistics, and detection-module state reset per request, so each
  response's ``meta.resilience`` block describes *that* request (the
  same per-contract contract the CLI and bench rows keep).  Registry
  counters prefixed ``mythril_tpu_serve_*`` carry the server-lifetime
  totals instead.
- **Deadline budget** — the request's wall-clock budget is installed in
  ``resilience/budget.py`` before execution and cleared after; an
  expiring budget drains the analysis at a transaction boundary through
  the same cooperative checkpoints a SIGTERM walks, and the response
  ships ``partial: true`` with whatever the boundary held.
- **Failure scope** — an unhandled executor crash (or an injected
  ``serve_crash``) fails *that request* with a flight-recorder dump
  attached to the error body, records a breaker failure for the
  request's source, and decontaminates the shared state: blast context
  dropped, resident device pools reset, model cache cleared, coalescer
  queue purged.  The next request starts from a cold-but-consistent
  pool; the process never dies.
- **Device demotion** — a mid-request device-health demotion
  (watchdog re-probe failure) flips the engine to degraded host-CDCL
  mode: requests keep completing (the CDCL tail answers everything),
  and ``/readyz`` surfaces ``"mode": "host-cdcl"`` so the fleet can
  rebalance instead of the process dying.
"""

import collections
import logging
import math
import threading
import time
import uuid

from mythril_tpu.observability.metrics import get_registry
from mythril_tpu.serve.admission import AdmissionQueue, Ticket
from mythril_tpu.serve.config import ServeConfig

log = logging.getLogger(__name__)

#: margin added to the laser execution timeout over the budget: the
#: budget (drain semantics, partial report) must always govern; the
#: laser's own timeout is only the backstop behind it
_EXEC_TIMEOUT_MARGIN_S = 30.0


class AnalysisEngine:
    """Single-consumer analysis worker over an :class:`AdmissionQueue`."""

    def __init__(self, queue: AdmissionQueue, config: ServeConfig):
        self.queue = queue
        self.config = config
        #: serving-fabric router (serve/fabric.py), attached by the
        #: server when --fleet-listen is configured; None = every
        #: request runs in-process
        self.router = None
        self.requests_done = 0
        self.requests_failed = 0
        self.requests_partial = 0
        self.in_flight = None  # request id while executing
        # live introspection (/debug/requests): the in-flight request's
        # descriptor and a bounded history of recently finished ones
        self.in_flight_info = None
        self.recent_requests = collections.deque(maxlen=16)
        self.started_at = time.time()
        self._thread = threading.Thread(
            target=self._run, name="mythril-serve-engine", daemon=True
        )
        registry = get_registry()
        self._m_total = registry.counter(
            "mythril_tpu_serve_requests_total",
            "requests executed (all outcomes)",
        )
        self._m_failed = registry.counter(
            "mythril_tpu_serve_failures_total",
            "requests failed by an executor crash",
        )
        self._m_partial = registry.counter(
            "mythril_tpu_serve_partial_total",
            "requests answered with a partial (deadline-drained) report",
        )
        self._m_expired_queue = registry.counter(
            "mythril_tpu_serve_expired_in_queue_total",
            "requests whose budget expired before execution started",
        )
        self._m_latency = registry.histogram(
            "mythril_tpu_serve_request_seconds",
            "end-to-end request latency (admission to response)",
        )

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        self._configure_process()
        self._thread.start()

    def join(self, timeout=None) -> None:
        self._thread.join(timeout)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    @staticmethod
    def _configure_process() -> None:
        """Server-mode defaults on the args bus: no per-request
        checkpoint journaling (the daemon's durability is the queue,
        not a journal), coalescer in cross-request mode."""
        from mythril_tpu.ops.coalesce import set_serve_mode
        from mythril_tpu.support.support_args import args

        args.checkpoint_dir = None
        args.resume_from = None
        set_serve_mode(True)
        # the knowledge store loads ONCE at engine start (not lazily on
        # the first request), so the first request already warm-starts
        # and a corrupt store is quarantined before traffic arrives
        from mythril_tpu.persist.plane import get_knowledge_plane

        plane = get_knowledge_plane()
        if plane.active:
            plane.store  # open + load + register the atexit flush

    def debug_requests(self) -> dict:
        """The ``/debug/requests`` body: the in-flight request (phase =
        the engine thread's innermost open span, deadline budget
        remaining, per-tier lane counts so far) plus a bounded history
        of finished ones.  Read from HTTP handler threads — everything
        here is an advisory snapshot, nothing locks the engine."""
        from mythril_tpu.observability import get_tracer
        from mythril_tpu.observability.ledger import get_ledger
        from mythril_tpu.resilience.budget import current_budget

        in_flight = None
        info = self.in_flight_info
        if info is not None:
            budget = current_budget()
            in_flight = dict(info)
            elapsed = time.monotonic() - in_flight.pop(
                "started_monotonic"
            )
            in_flight["elapsed_s"] = round(elapsed, 3)
            in_flight["budget_remaining_s"] = (
                round(budget.remaining_s(), 3) if budget else None
            )
            phase = None
            tid = self._thread.ident
            if tid is not None:
                phase = get_tracer().live_spans().get(tid)
            in_flight["phase"] = phase
            in_flight["lanes_by_tier"] = get_ledger().scope_snapshot(
                info["request_id"]
            )
        return {
            "in_flight": in_flight,
            "recent": list(self.recent_requests),
            "requests": {
                "done": self.requests_done,
                "failed": self.requests_failed,
                "partial": self.requests_partial,
            },
            "queue_depths": self.queue.depths(),
        }

    def degraded(self) -> bool:
        """True when the device was demoted (fires no fault point)."""
        from mythril_tpu.ops import device_health

        return device_health.demotion() is not None

    def mode(self) -> str:
        return "host-cdcl" if self.degraded() else "device"

    # -- the loop -------------------------------------------------------

    def _run(self) -> None:
        from mythril_tpu.resilience.checkpoint import _drain_event

        while True:
            if _drain_event.is_set():
                # process drain (SIGTERM): stop executing; the server
                # fails queued tickets and flushes artifacts
                break
            ticket = self.queue.pop(timeout=0.25)
            if ticket is None:
                if self.queue.closed:
                    break
                continue
            try:
                self._execute(ticket)
            except Exception:  # noqa: BLE001 — the engine never dies
                log.exception("engine: ticket fell through all handlers")
                ticket.resolve(500, {
                    "error": {
                        "code": "internal",
                        "message": "request handling failed",
                    }
                })

    # -- per-request execution -----------------------------------------

    def _execute(self, ticket: Ticket) -> None:
        request = ticket.request
        if ticket.abandoned.is_set():
            # the client hung up while this sat in the queue: spending
            # engine (or fabric seat) time on it starves live callers
            ticket.resolve(499, {"error": {
                "code": "client_gone",
                "message": "client disconnected while queued",
            }})
            return
        rid = uuid.uuid4().hex[:12]
        deadline_s = request.deadline_s or self.config.default_deadline_s
        budget_s = deadline_s - ticket.queued_s()
        self._m_total.inc()
        if budget_s <= 0:
            # the budget drained away in the queue: answering with an
            # empty "partial" analysis would waste engine time the
            # requests behind this one were promised
            self._m_expired_queue.inc()
            ticket.resolve(504, {
                "error": {
                    "code": "expired_in_queue",
                    "message": "request deadline expired while queued",
                    "queued_s": round(ticket.queued_s(), 3),
                }
            })
            return

        trace_id = request.trace_id
        if trace_id is None:
            from mythril_tpu.observability import new_trace_id

            trace_id = new_trace_id()
        self.in_flight = rid
        self.in_flight_info = {
            "request_id": rid,
            "trace_id": trace_id,
            "contract": request.name,
            "source": request.source,
            "priority": request.priority,
            "budget_s": round(budget_s, 3),
            "started_monotonic": time.monotonic(),
        }
        began = time.monotonic()
        try:
            status, body = self._analyze(ticket, rid, trace_id,
                                         budget_s)
        finally:
            self.in_flight = None
            self.in_flight_info = None
        elapsed = time.monotonic() - began
        self._m_latency.observe(ticket.queued_s())
        self.requests_done += 1
        ok = status < 500
        self.queue.record_outcome(request.source, ok)
        # charge the tenant's rolling quota window with the wall time
        # this request actually consumed (in-process or fabric seat)
        self.queue.note_usage(request.source, elapsed)
        if not ok:
            self.requests_failed += 1
            self._m_failed.inc()
        if isinstance(body, dict):
            body.setdefault("request_id", rid)
            body.setdefault("trace_id", trace_id)
            body.setdefault("analysis_s", round(elapsed, 3))
        self.recent_requests.appendleft({
            "request_id": rid,
            "trace_id": trace_id,
            "contract": request.name,
            "source": request.source,
            "status": status,
            "partial": bool(
                isinstance(body, dict) and body.get("partial")
            ),
            "analysis_s": round(elapsed, 3),
        })
        ticket.resolve(status, body)

    def _analyze(self, ticket: Ticket, rid: str, trace_id: str,
                 budget_s: float):
        """Run one analysis inside the full isolation scope; returns
        (status, body) and never raises."""
        from mythril_tpu.observability import set_trace_id, spans as obs
        from mythril_tpu.resilience import budget as request_budget

        request = ticket.request
        try:
            # the request's trace identity governs everything this
            # execution produces: the span tree, the lane-ledger scope,
            # the coalescer stamps, and — through the fleet payload —
            # any worker processes it spawns
            set_trace_id(trace_id)
            with obs.span("serve.request", cat="serve", rid=rid,
                          trace_id=trace_id,
                          source=request.source, contract=request.name,
                          priority=request.priority):
                self._reset_request_scope(rid, trace_id)
                request_budget.install_budget(
                    budget_s, label=f"{request.source}/{rid}"
                )
                # resource governor rides the same per-request scope as
                # the wall-clock budget: a state-explosion request
                # degrades to a partial verdict instead of taking the
                # serving process (the serve path bypasses
                # MythrilAnalyzer, so it arms its own)
                from mythril_tpu.resilience.governor import (
                    clear_governor, install_governor,
                )

                install_governor(label=f"{request.source}/{rid}")
                try:
                    if self.router is not None:
                        # fabric first: a connected seat answers the
                        # request off-box; None walks the degradation
                        # ladder down to in-process execution
                        routed = self.router.execute(
                            ticket, request, rid, trace_id, budget_s
                        )
                        if routed is not None:
                            status, body = routed
                            if isinstance(body, dict) and body.get(
                                "partial"
                            ):
                                self.requests_partial += 1
                                self._m_partial.inc()
                            return status, body
                    return 200, self._fire(request, rid, budget_s)
                finally:
                    clear_governor()
                    request_budget.clear_budget()
        except Exception as exc:  # noqa: BLE001 — isolate the request
            return 500, self._fail_request(rid, request, exc)

    def _reset_request_scope(self, rid: str,
                             trace_id: str = None) -> None:
        """Per-request state: telemetry scopes and detection modules
        reset; the WARM solver state (blast context, resident pool,
        memo channels, model cache) deliberately survives — that
        amortization is the daemon's reason to exist.
        ``MYTHRIL_TPU_SERVE_COLD=1`` resets it too (parity debugging)."""
        from mythril_tpu.analysis.module.loader import ModuleLoader
        from mythril_tpu.ops.async_dispatch import (
            async_stats, get_async_dispatcher,
        )
        from mythril_tpu.ops.batched_sat import dispatch_stats
        from mythril_tpu.ops.coalesce import set_request_scope
        from mythril_tpu.resilience.checkpoint import get_checkpoint_plane
        from mythril_tpu.smt.solver import SolverStatistics

        if self.config.cold_per_request:
            self._decontaminate("cold-per-request")
        get_async_dispatcher().drop()
        for module in ModuleLoader().get_detection_modules():
            module.reset_module()
            module.cache.clear()
        dispatch_stats.reset()
        async_stats.reset()
        stats = SolverStatistics()
        stats.enabled = True
        stats.reset()
        # the partial flag is per-request in serve mode: a prior
        # request's deadline drain must not mark this one partial
        get_checkpoint_plane().partial = False
        set_request_scope(rid, trace_id)
        # lane-ledger origin: records produced by this request carry
        # its contract name, scope and trace id (/debug/lanes keys the
        # per-scope aggregates on rid)
        from mythril_tpu.observability.ledger import set_origin

        set_origin(contract=self.in_flight_info["contract"]
                   if self.in_flight_info else None,
                   tx_index=None, scope=rid, trace=trace_id)

    def _fire(self, request, rid: str, budget_s: float) -> dict:
        """The analysis proper (the bench/_analyze_one shape), plus the
        response body."""
        import json as _json

        from mythril_tpu.analysis.report import Report
        from mythril_tpu.analysis.security import fire_lasers
        from mythril_tpu.analysis.symbolic import SymExecWrapper
        from mythril_tpu.laser.ethereum.time_handler import time_handler
        from mythril_tpu.resilience import faults
        from mythril_tpu.resilience.budget import current_budget
        from mythril_tpu.resilience.checkpoint import (
            drain_requested, get_checkpoint_plane,
        )
        from mythril_tpu.solidity.evmcontract import EVMContract

        faults.maybe_fault_request()  # chaos seam: poisoned request
        exec_timeout = math.ceil(budget_s + _EXEC_TIMEOUT_MARGIN_S)
        time_handler.start_execution(exec_timeout)
        contract = EVMContract(code=request.code, name=request.name)
        began = time.monotonic()
        sym = SymExecWrapper(
            contract,
            address=0x901D12EBE1B195E5AA8748E62BD7734AE19B51F,
            strategy="bfs",
            max_depth=request.max_depth,
            execution_timeout=exec_timeout,
            create_timeout=10,
            transaction_count=request.tx_count,
            modules=request.modules,
            compulsory_statespace=False,
        )
        issues = fire_lasers(sym, request.modules)
        analysis_s = time.monotonic() - began

        report = Report(contracts=[contract])
        for issue in issues:
            report.append_issue(issue)
        # render INSIDE the budget scope: the partial flag rides
        # drain_requested(), which reads the installed budget
        rendered = _json.loads(report.as_swc_standard_format())[0]
        partial = bool(
            drain_requested() or get_checkpoint_plane().partial
        )
        if partial:
            self.requests_partial += 1
            self._m_partial.inc()
            # a drained request's deferred lanes must not ride into a
            # later request's device batch — purge its coalescer scope
            from mythril_tpu.ops.coalesce import purge_scope

            purge_scope(rid)
        budget = current_budget()
        body = {
            "request_id": rid,
            "name": request.name,
            "issues": rendered["issues"],
            "findings_swc": sorted(
                {i.swc_id for i in issues if i.swc_id}
            ),
            "meta": rendered["meta"],
            "partial": partial,
            "aborted_at_tx": getattr(sym.laser, "aborted_at_tx", None),
            "analysis_s": round(analysis_s, 3),
            "budget_s": round(budget_s, 3),
            "budget_remaining_s": round(
                budget.remaining_s(), 3
            ) if budget else None,
            "mode": self.mode(),
        }
        try:
            from mythril_tpu.persist.plane import (
                code_digest, get_knowledge_plane,
            )

            # a finished, non-partial verdict becomes the admission
            # edge's report cache entry (partial bodies are refused by
            # report_cache_put itself); inert without a persist dir
            get_knowledge_plane().report_cache_put(
                code_digest(request.code), request.tx_count,
                request.max_depth, request.modules, body,
            )
        except Exception:  # noqa: BLE001 — caching never fails a request
            log.debug("persist: report cache store failed", exc_info=True)
        return body

    def _fail_request(self, rid: str, request, exc) -> dict:
        """The isolation contract for a crashed request: flight dump
        attached, shared state decontaminated, structured error out —
        the engine (and so the server) keeps going."""
        from mythril_tpu.observability import flight

        log.error("request %s (%s) crashed: %s", rid, request.source,
                  exc, exc_info=True)
        dump_path = flight.get_flight_recorder().dump("serve_request")
        self._decontaminate(f"request {rid} crashed")
        return {
            "error": {
                "code": "analysis_failed",
                "message": f"{type(exc).__name__}: {exc}",
                "flight_dump": dump_path,
            },
            "request_id": rid,
        }

    @staticmethod
    def _decontaminate(reason: str) -> None:
        """Drop every piece of shared mutable solver state a crashed
        request may have left inconsistent.  Generation scoping does
        the heavy lifting: a fresh blast context moves the generation,
        and the resident-pool reset drops device buffers keyed to the
        old one."""
        log.warning("decontaminating shared solver state (%s)", reason)
        from mythril_tpu.ops.async_dispatch import get_async_dispatcher
        from mythril_tpu.ops.batched_sat import reset_resident_pools
        from mythril_tpu.ops.coalesce import reset_coalescer
        from mythril_tpu.smt.solver import reset_blast_context
        from mythril_tpu.support.model import clear_model_cache

        try:
            get_async_dispatcher().drop()
        except Exception:  # noqa: BLE001 — best-effort teardown
            log.debug("async drop failed during decontamination",
                      exc_info=True)
        reset_blast_context()
        clear_model_cache()
        reset_resident_pools()
        reset_coalescer(hard=True)
