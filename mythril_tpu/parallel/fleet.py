"""Fault-tolerant frontier fleet: shard the LASER world-state frontier
across worker processes.

``myth analyze --workers N`` (or ``MYTHRIL_TPU_FLEET_WORKERS=N``) turns
the transaction loop's frontier into leased subtrees: at the first
transaction boundary holding at least two open world-states, the
coordinator (``parallel/coordinator.py``) writes each subtree as a PR-3
journal, leases the journals to N worker processes, and each worker
runs the full existing dispatch plane (word tier -> frontier rounds ->
CDCL tail) against its subtree by *resuming* from the lease journal.
Findings merge back under the detection modules' own dedup keys, so
the union over subtrees is the single-process finding set by
construction — exploration is idempotent and the merge is the same
address-keyed cache the sequential path uses.

Robustness is the headline (docs/scaling.md has the failure matrix):
heartbeat-driven failure detection with lease expiry, re-lease from the
dead worker's last journal boundary, straggler subtree splitting, and
epoch-fenced knowledge gossip (``parallel/gossip.py``) so a zombie
worker resuming after a partition cannot poison the shared channels.
Loss of *every* worker degrades to in-process execution of the
remaining lease journals — never a failed analysis.

Kill switch: ``MYTHRIL_TPU_FLEET=0`` (or ``--workers 0``) is the exact
current single-process path — the svm seam short-circuits before any
fleet code loads.
"""

import logging
import os
import pickle
import queue
import shutil
import socket
import sys
import tempfile
import threading
import time
from typing import List, Optional

log = logging.getLogger(__name__)

_PREFIX = "mythril_tpu_fleet_"

#: field -> help; mirrored into bench rows as ``fleet_<field>`` and the
#: jsonv2 ``meta.resilience`` block (nonzero only), same shim pattern
#: as resilience/telemetry.py so the registry stays the single store
_FIELDS = {
    "leases": "subtree leases granted (initial + re-leases + splits)",
    "rebalances": "straggler subtrees split and re-leased",
    "worker_deaths": "workers declared dead (TTL, crash, disconnect)",
    "gossip_sent": "knowledge messages accepted and routed",
    "gossip_dropped_stale": "messages fenced for a stale lease epoch",
    "auth_rejects": "connections rejected by the fabric handshake",
    "frame_rejects": "malformed/tampered/oversized frames struck",
    "remote_attaches": "externally-launched workers attached",
    # persist plane (persist/plane.py): knowledge deltas riding
    # heartbeat frames between seats, and deltas applied+absorbed on
    # the coordinator side
    "persist_deltas_sent": "knowledge deltas sent on heartbeats",
    "persist_deltas_applied": "heartbeat knowledge deltas applied",
}


class FleetStats:
    """Fleet counters over the unified metrics registry
    (``mythril_tpu_fleet_*``); reset per analyzed contract alongside
    ``DispatchStats``."""

    __slots__ = ()

    @staticmethod
    def _cell(field: str):
        from mythril_tpu.observability.metrics import get_registry

        return get_registry().counter(_PREFIX + field, _FIELDS[field])

    def reset(self):
        for field in _FIELDS:
            self._cell(field).set(0)

    def __getattr__(self, name):
        if name in _FIELDS:
            return self._cell(name).value
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if name not in _FIELDS:
            raise AttributeError(
                f"unknown fleet counter {name!r} (registered: "
                f"{tuple(_FIELDS)})"
            )
        self._cell(name).set(value)

    def as_dict(self):
        return {field: self._cell(field).value for field in _FIELDS}


fleet_stats = FleetStats()


# ---------------------------------------------------------------------------
# knobs / roles
# ---------------------------------------------------------------------------


def _killed() -> bool:
    return os.environ.get("MYTHRIL_TPU_FLEET", "").lower() in (
        "0", "off", "false",
    )


def worker_role() -> bool:
    return os.environ.get("MYTHRIL_TPU_FLEET_ROLE") == "worker"


def effective_workers() -> int:
    """``--workers`` (args bus) wins; the env default covers daemon /
    bench configuration.  0 anywhere = fleet off."""
    if _killed():
        return 0
    from mythril_tpu.support.support_args import args

    configured = getattr(args, "fleet_workers", None)
    if configured is None:
        try:
            configured = int(
                os.environ.get("MYTHRIL_TPU_FLEET_WORKERS", "0")
            )
        except ValueError:
            configured = 0
    return max(0, int(configured))


def seam_enabled() -> bool:
    """Cheap gate the svm loop consults: anything fleet-shaped to do at
    a transaction boundary?  False = the exact single-process path."""
    if worker_role():
        return True  # gossip/heartbeat boundary duties
    return effective_workers() > 0


def min_states() -> int:
    """Smallest frontier worth sharding (default 2).  ``1`` is
    legitimate: the whole remaining analysis is delegated as a single
    lease at the first boundary — full-offload mode, and the test that
    proves every finding can ride the worker->coordinator merge."""
    try:
        return max(1, int(os.environ.get(
            "MYTHRIL_TPU_FLEET_MIN_STATES", "2"
        )))
    except ValueError:
        return 2


def should_delegate(laser) -> bool:
    from mythril_tpu.resilience.checkpoint import drain_requested

    if worker_role() or effective_workers() < 1:
        return False
    if getattr(laser, "_fleet_attempted", False):
        return False
    if drain_requested():
        return False
    return len(laser.open_states) >= min_states()


def svm_boundary(laser, address: int, tx_index: int) -> bool:
    """The one seam ``LaserEVM._execute_transactions`` calls per
    transaction boundary (only when :func:`seam_enabled`).  In a worker
    it performs the boundary duties (apply/send gossip, fault seam) and
    returns False; in the coordinating process it may delegate the
    remaining transactions to the fleet — True means the fleet (plus
    any in-process fallback) completed them and the caller stops."""
    if worker_role():
        session = _worker_session
        if session is not None:
            session.tx_boundary(tx_index)
        return False
    if not should_delegate(laser):
        return False
    laser._fleet_attempted = True
    try:
        return run_fleet(laser, address, tx_index)
    except Exception:  # noqa: BLE001 — the fleet must never fail an
        #               analysis the single-process path could finish
        log.exception("fleet: delegation failed; continuing in-process")
        return False


# ---------------------------------------------------------------------------
# coordinator side: shard, lease, merge, degrade
# ---------------------------------------------------------------------------


def _target_bytecode(states, address: int) -> Optional[str]:
    """Runtime bytecode of the analysis target, read out of the
    frontier itself (the frontier export seam: world-states are the
    authoritative carrier of the code under analysis)."""
    for world_state in states:
        try:
            account = world_state.accounts.get(int(address))
        except AttributeError:
            account = None
        if account is None:
            continue
        bytecode = getattr(getattr(account, "code", None), "bytecode", "")
        if bytecode:
            return bytecode
    return None


def _args_snapshot() -> dict:
    """The args-bus knobs a worker must mirror (simple-typed only;
    journaling/fleet/artifact knobs are per-process and overridden in
    the worker)."""
    from mythril_tpu.support.support_args import args

    skip = {"checkpoint_dir", "resume_from", "trace_out", "metrics_out",
            "fleet_workers"}
    return {
        key: value for key, value in vars(args).items()
        if key not in skip
        and isinstance(value, (bool, int, float, str, type(None)))
    }


def _frontier_chunks(states: List, shards: int) -> List[List]:
    """Round-robin partition: neighboring frontier states are usually
    siblings with near-identical cost, so striping balances depth
    skew better than contiguous slabs."""
    chunks = [[] for _ in range(shards)]
    for index, state in enumerate(states):
        chunks[index % shards].append(state)
    return [chunk for chunk in chunks if chunk]


def _write_lease_journal(directory: str, address: int, tx_index: int,
                         transaction_count: int, states: List,
                         findings: Optional[dict] = None) -> None:
    from mythril_tpu.resilience.checkpoint import write_journal

    write_journal(directory, {
        "kind": "mythril-tpu-checkpoint",
        "address": int(address),
        "tx_index": int(tx_index),
        "transaction_count": int(transaction_count),
        "open_states": list(states),
        "findings": findings or {"issues": {}, "caches": {}},
        "channels": {},
        "partial": False,
    })


def split_lease_journal(journal_dir: str):
    """Carve a lease's newest journal into two half-frontier journals
    (the straggler split).  Returns ``[(dir, tx_index, n_states), ...]``
    or None when the boundary frontier is not splittable."""
    from mythril_tpu.resilience.checkpoint import load_journal

    try:
        payload = load_journal(journal_dir)
    except Exception:  # noqa: BLE001 — a torn journal means no split
        log.warning("fleet: split aborted, journal unreadable",
                    exc_info=True)
        return None
    if payload is None:
        return None
    states = list(payload.get("open_states", ()))
    if len(states) < 2:
        return None
    half = (len(states) + 1) // 2
    halves = []
    for tag, chunk in (("a", states[:half]), ("b", states[half:])):
        directory = journal_dir.rstrip(os.sep) + f".split-{tag}"
        _write_lease_journal(
            directory, payload["address"], payload["tx_index"],
            payload["transaction_count"], chunk,
            findings=payload.get("findings"),
        )
        halves.append((directory, int(payload["tx_index"]), len(chunk)))
    return halves


def apply_gossip_local(body: bytes) -> None:
    """Coordinator-side application of a routed knowledge payload (so
    an in-process fallback after total fleet loss starts warm)."""
    try:
        from mythril_tpu.parallel.gossip import apply_knowledge
        from mythril_tpu.smt.solver import get_blast_context

        apply_knowledge(get_blast_context(), body)
    except Exception:  # noqa: BLE001 — knowledge is optional
        log.debug("fleet: local gossip apply failed", exc_info=True)


def _merge_findings(findings: dict) -> int:
    """Fold a worker's detection-module snapshot into this process's
    modules under the modules' own address-keyed dedup (the exact
    suppression the sequential path applies via ``module.cache``).
    Returns the number of newly-accepted issues."""
    from mythril_tpu.analysis.module.loader import ModuleLoader

    accepted = 0
    issues_by_module = (findings or {}).get("issues", {})
    caches_by_module = (findings or {}).get("caches", {})
    for module in ModuleLoader().get_detection_modules():
        name = type(module).__name__
        for issue in issues_by_module.get(name, ()):  # lease-id order
            if issue.address in module.cache:
                continue
            module.issues.append(issue)
            module.cache.add(issue.address)
            accepted += 1
        module.cache |= set(caches_by_module.get(name, ()))
    return accepted


def _merge_result(lease, tracer) -> None:
    """One finished lease's result: findings, spans, telemetry."""
    if not lease.result_body:
        return
    try:
        body = pickle.loads(lease.result_body)
    except Exception:  # noqa: BLE001 — a torn result costs re-merge
        #               via the journal, never the analysis
        log.warning("fleet: result body unreadable for %s",
                    lease.lease_id, exc_info=True)
        return
    _merge_findings(body.get("findings"))
    from mythril_tpu.observability.ledger import get_ledger

    get_ledger().merge_snapshot(body.get("ledger"))
    worker_id = (lease.result or {}).get("worker_id", "?")
    wall_s = float((lease.result or {}).get("wall_s", 0.0))
    if tracer is not None:
        tracer.add_external_total(f"fleet.worker:{worker_id}", wall_s)
        events = body.get("spans")
        if events:
            # named absorb: the stream gets its own synthetic Perfetto
            # pid (a respawned worker reusing a dead worker's OS pid
            # must not merge into its track) and every event is
            # re-parented under the request's trace id
            tracer.absorb_events(
                events, worker=str(worker_id),
                trace_id=(lease.result or {}).get("trace_id")
                or tracer.trace_id,
            )


def _explore_inprocess(laser, address: int, tx_index: int,
                       states: List) -> None:
    """Run transactions ``tx_index..transaction_count`` over a thawed
    subtree inside THIS process — the all-workers-dead degradation.
    Mirrors the `_execute_transactions` loop body; module hooks fire
    and dedup exactly as a sequential run."""
    from mythril_tpu.laser.batch import prune_infeasible
    from mythril_tpu.laser.ethereum.svm import _WorldStateView
    from mythril_tpu.laser.ethereum.transaction import (
        execute_message_call,
    )
    from mythril_tpu.resilience.checkpoint import drain_requested

    laser.open_states = list(states)
    for i in range(tx_index, laser.transaction_count):
        if not laser.open_states or drain_requested():
            break
        laser.open_states = [
            view.world_state for view in prune_infeasible(
                [_WorldStateView(ws) for ws in laser.open_states]
            )
        ]
        laser._execute_hooks(laser._start_exec_hooks)
        execute_message_call(laser, address)
        laser._execute_hooks(laser._stop_exec_hooks)


def _finish_lease_inprocess(laser, address: int, lease) -> bool:
    """Resume one unfinished lease from its journal, in-process."""
    from mythril_tpu.resilience.checkpoint import load_journal

    try:
        payload = load_journal(lease.journal_dir)
    except Exception:  # noqa: BLE001
        payload = None
    if payload is None:
        log.error("fleet: lease %s has no readable journal; its "
                  "subtree is re-run from the delegation boundary",
                  lease.lease_id)
        return False
    _merge_findings(payload.get("findings"))
    _explore_inprocess(
        laser, address, int(payload["tx_index"]),
        list(payload.get("open_states", ())),
    )
    return True


def run_fleet(laser, address: int, tx_index: int) -> bool:
    """Shard ``laser.open_states`` into leases and drive them to
    completion across worker processes (with in-process fallback for
    whatever the fleet could not finish).  Returns True when the
    remaining transactions are fully accounted for; False only when
    the fleet could not even start (caller continues unchanged)."""
    from mythril_tpu.observability import spans as obs
    from mythril_tpu.parallel.coordinator import (
        Coordinator, FleetConfig,
    )
    from mythril_tpu.resilience.checkpoint import (
        CheckpointPlane, drain_requested, get_checkpoint_plane,
    )

    workers = effective_workers()
    states = CheckpointPlane._frontier_snapshot(laser.open_states)
    bytecode = _target_bytecode(states, address)
    if bytecode is None:
        log.warning("fleet: target bytecode not found in the frontier; "
                    "staying single-process")
        return False
    max_depth = laser.max_depth
    payload = {
        "name": "fleet-target",
        "address": int(address),
        "code": bytecode,
        "transaction_count": int(laser.transaction_count),
        "max_depth": (
            int(max_depth) if max_depth not in (None, float("inf"))
            else None
        ),
        "execution_timeout": int(laser.execution_timeout or 0) or None,
        "create_timeout": int(laser.create_timeout or 0) or None,
        "args": _args_snapshot(),
        "trace": bool(obs.get_tracer().enabled),
        # the request/run trace identity crosses the process boundary
        # in the lease payload: workers stamp their span streams with
        # it and the coordinator re-parents them under it on absorb,
        # so one `--workers N` analysis renders as ONE Perfetto trace
        "trace_id": obs.get_trace_id(),
    }
    config = FleetConfig.from_env(workers)
    base_dir = tempfile.mkdtemp(prefix="mtpu-fleet-")
    coordinator = Coordinator(config, payload)
    shards = min(workers, len(states))
    for index, chunk in enumerate(_frontier_chunks(states, shards)):
        lease_dir = os.path.join(base_dir, f"lease{index}")
        _write_lease_journal(
            lease_dir, address, tx_index, laser.transaction_count,
            chunk,
        )
        coordinator.add_lease(lease_dir, tx_index, len(chunk))
    coordinator.open_listener()
    coordinator.open_debug_listener()
    began = time.monotonic()
    try:
        with obs.span("fleet.run", cat="fleet", leases=shards,
                      workers=workers, tx=tx_index):
            coordinator.run()
    finally:
        coordinator.shutdown()
    tracer = obs.get_tracer() if obs.get_tracer().enabled else None
    for lease in sorted(coordinator.finished(),
                        key=lambda l: l.lease_id):
        _merge_result(lease, tracer)
    partial = False
    for lease in sorted(coordinator.unfinished(),
                        key=lambda l: l.lease_id):
        if drain_requested():
            partial = True
            # merge what the lease journal already holds; the rest is
            # the partial report's honest gap (same as a drained
            # single-process run)
            from mythril_tpu.resilience.checkpoint import load_journal

            try:
                journal = load_journal(lease.journal_dir)
            except Exception:  # noqa: BLE001
                journal = None
            if journal is not None:
                _merge_findings(journal.get("findings"))
            continue
        _finish_lease_inprocess(laser, address, lease)
    if any(
        (lease.result or {}).get("partial") for lease in
        coordinator.finished()
    ):
        partial = True
    if partial:
        laser.aborted_at_tx = tx_index
        get_checkpoint_plane().partial = True
    log.info(
        "fleet: %d lease(s) done (%d in-process), %d worker deaths, "
        "%d rebalances, %.1fs",
        len(coordinator.finished()), len(coordinator.unfinished()),
        fleet_stats.worker_deaths, fleet_stats.rebalances,
        time.monotonic() - began,
    )
    laser.open_states = []
    if os.environ.get("MYTHRIL_TPU_FLEET_KEEP_JOURNALS") != "1":
        shutil.rmtree(base_dir, ignore_errors=True)
    return True


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------


class _WorkerSession:
    """State shared between the worker's comms threads and its analysis
    thread: the active lease, the gossip inbox, and the send lock."""

    def __init__(self, worker_id: str, conn: socket.socket,
                 channel=None):
        self.worker_id = worker_id
        self.conn = conn
        #: authenticated frame channel (``fabric.AuthedChannel``), or
        #: None for the legacy bare-frame localhost path
        self.channel = channel
        self.send_lock = threading.Lock()
        self.lease_header: Optional[dict] = None
        self.lease_lock = threading.Lock()
        self.gossip_in: "queue.Queue" = queue.Queue()
        self.lease_queue: "queue.Queue" = queue.Queue()
        self.closed = False
        #: local journal directory for a journal-over-the-wire lease
        #: (remote attach: no filesystem shared with the coordinator)
        self.wire_dir: Optional[str] = None

    # -- comms ----------------------------------------------------------

    def send(self, header: dict, body: bytes = b"") -> None:
        from mythril_tpu.parallel.gossip import FrameError, send_frame

        if self.closed:
            return
        try:
            with self.send_lock:
                if self.channel is not None:
                    self.channel.send(header, body)
                else:
                    send_frame(self.conn, header, body)
        except (FrameError, OSError):
            self.closed = True

    def reader_loop(self) -> None:
        from mythril_tpu.parallel.gossip import FrameError, recv_frame

        while True:
            try:
                if self.channel is not None:
                    header, body = self.channel.recv()
                else:
                    header, body = recv_frame(self.conn)
            except (FrameError, OSError):
                self.closed = True
                self._abort_active_lease()
                self.lease_queue.put(None)
                return
            kind = header.get("type")
            if kind == "lease":
                self.lease_queue.put((header, body))
            elif kind == "gossip":
                self.gossip_in.put((header, body))
            elif kind == "revoke":
                self._on_revoke(header)
            elif kind == "drain":
                # the frame twin of SIGTERM for remote workers:
                # checkpoint at the next boundary, report partial, exit
                from mythril_tpu.resilience.checkpoint import (
                    request_drain,
                )

                request_drain("coordinator drain frame")
            elif kind == "shutdown":
                # a graceful coordinator stop: for a spawned worker
                # this is the end (redial budget 0); for a remote
                # ``--reconnect`` worker it is a pause — worker_main's
                # redial budget decides which
                self.closed = True
                self._abort_active_lease()
                self.lease_queue.put(None)
                return

    def _abort_active_lease(self) -> None:
        """The coordinator is gone mid-lease: expire the running
        analysis's budget so it drains at its next boundary instead of
        finishing a result nobody will read — the seat must get back
        to redialing in seconds, not after the full execution
        timeout."""
        from mythril_tpu.resilience.budget import install_budget

        with self.lease_lock:
            header = self.lease_header
        if header is not None:
            install_budget(0.0, label="coordinator lost")

    def _on_revoke(self, header: dict) -> None:
        """Request-scoped revocation (serve client abort): expire the
        active lease's budget so the analysis drains at its next
        boundary.  Non-sticky — this worker stays leasable."""
        from mythril_tpu.resilience.budget import install_budget

        with self.lease_lock:
            current = self.lease_header
        if (current is not None
                and header.get("lease_id") == current["lease_id"]):
            install_budget(0.0, label="lease revoked")

    def heartbeat_loop(self, interval_holder: dict) -> None:
        while not self.closed:
            with self.lease_lock:
                header = self.lease_header
            if header is not None:
                hb = {
                    "type": "heartbeat",
                    "lease_id": header["lease_id"],
                    "stamp": header["stamp"],
                    "worker_id": self.worker_id,
                }
                # persist gossip rides the heartbeat frame: a knowledge
                # delta (plain freeze_knowledge body, same encoding as
                # a tx-boundary gossip) attaches whenever the channels
                # changed since the last beat.  Best-effort end to end:
                # a freeze racing the analysis thread, or a body past
                # MAX_FRAME, skips THIS beat's delta — the next beat
                # (or the tx boundary) carries it
                body = b""
                try:
                    from mythril_tpu.persist.plane import (
                        get_knowledge_plane,
                    )
                    from mythril_tpu.smt.solver import get_blast_context

                    delta = get_knowledge_plane().encode_heartbeat_delta(
                        get_blast_context()
                    )
                    if delta:
                        hb["persist"] = True
                        body = delta
                        fleet_stats.persist_deltas_sent += 1
                except Exception:  # noqa: BLE001 — heartbeats must beat
                    log.debug("worker: persist delta skipped",
                              exc_info=True)
                self.send(hb, body)
            time.sleep(interval_holder.get("s", 0.5))

    # -- boundary duties (called from the svm seam) ---------------------

    def tx_boundary(self, tx_index: int) -> None:
        """Apply queued inbound knowledge, publish ours, and hit the
        preemption fault seam — all at the only point where no dispatch
        is in flight and the channels are consistent."""
        from mythril_tpu.parallel.gossip import (
            freeze_knowledge, stamp_for,
        )
        from mythril_tpu.resilience.faults import maybe_fault_worker_kill
        from mythril_tpu.smt.solver import get_blast_context

        maybe_fault_worker_kill()
        with self.lease_lock:
            header = self.lease_header
        if header is None:
            return
        ctx = get_blast_context()
        while True:
            try:
                _gheader, body = self.gossip_in.get_nowait()
            except queue.Empty:
                break
            try:
                from mythril_tpu.parallel.gossip import apply_knowledge

                apply_knowledge(ctx, body)
            except Exception:  # noqa: BLE001 — knowledge is optional
                log.debug("worker: gossip apply failed", exc_info=True)
        try:
            epoch = int(header["stamp"].get("lease_epoch", 0))
            self.send(
                {
                    "type": "gossip",
                    "lease_id": header["lease_id"],
                    "stamp": stamp_for(ctx, epoch).as_dict(),
                    "worker_id": self.worker_id,
                    "tx": tx_index,
                },
                freeze_knowledge(ctx),
            )
        except Exception:  # noqa: BLE001
            log.debug("worker: gossip send failed", exc_info=True)
        self.ship_checkpoint(header)
        # boundary flush for the knowledge store (no-op when inert):
        # the same "no dispatch in flight" guarantee that makes gossip
        # safe here makes the freeze-for-disk safe
        from mythril_tpu.persist.plane import get_knowledge_plane

        get_knowledge_plane().maybe_flush()

    def ship_checkpoint(self, header: dict) -> None:
        """Journal-over-the-wire: ship the local boundary journal back
        so the coordinator can re-lease from this exact boundary if we
        die — the remote twin of writing into a shared directory."""
        if self.wire_dir is None:
            return
        try:
            from mythril_tpu.parallel import fabric as fabric_mod

            self.send(
                {
                    "type": "checkpoint",
                    "lease_id": header["lease_id"],
                    "stamp": header["stamp"],
                    "worker_id": self.worker_id,
                },
                fabric_mod.pack_journal(self.wire_dir),
            )
        except Exception:  # noqa: BLE001 — a lost checkpoint costs
            #               repeated work on re-lease, never the result
            log.debug("worker: checkpoint ship failed", exc_info=True)


_worker_session: Optional[_WorkerSession] = None


def _worker_reset_scope(journal_dir: str, knobs: dict) -> None:
    """Per-lease isolation in the worker: the serve engine's reset
    sequence plus a full decontamination (leases may belong to
    different analyses when the pool is reused), then the lease journal
    becomes this process's checkpoint plane."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.ops.async_dispatch import (
        async_stats, get_async_dispatcher,
    )
    from mythril_tpu.ops.batched_sat import (
        dispatch_stats, reset_resident_pools,
    )
    from mythril_tpu.resilience import checkpoint
    from mythril_tpu.smt.solver import (
        SolverStatistics, reset_blast_context,
    )
    from mythril_tpu.support.model import clear_model_cache
    from mythril_tpu.support.support_args import args

    get_async_dispatcher().drop()
    reset_blast_context()
    clear_model_cache()
    reset_resident_pools()
    for module in ModuleLoader().get_detection_modules():
        module.reset_module()
        module.cache.clear()
    dispatch_stats.reset()
    async_stats.reset()
    # per-lease ledger scope: each lease's lanes ship home with ITS
    # result, so a worker serving a second lease must not re-ship the
    # first one's aggregates (origin stamps survive the reset)
    from mythril_tpu.observability.ledger import get_ledger

    get_ledger().reset()
    stats = SolverStatistics()
    stats.enabled = True
    stats.reset()
    # fresh checkpoint plane per lease (the sticky signal-drain case
    # exits before this runs): the lease journal IS this process's
    # journal — resume restores the subtree, progress writes back
    plane = checkpoint.get_checkpoint_plane()
    plane.partial = False
    plane.configure(journal_dir, resume=True)
    for key, value in knobs.items():
        if hasattr(args, key):
            setattr(args, key, value)
    args.fleet_workers = 0
    args.trace_out = None
    args.metrics_out = None
    args.checkpoint_dir = journal_dir
    args.resume_from = journal_dir


def _worker_lease_cleanup(session: _WorkerSession) -> None:
    from mythril_tpu.resilience.budget import clear_budget

    clear_budget()  # a revoke-expired budget must not leak forward
    if session.wire_dir is not None:
        shutil.rmtree(session.wire_dir, ignore_errors=True)
        session.wire_dir = None


def _worker_run_lease(session: _WorkerSession, header: dict,
                      body: bytes = b"") -> None:
    """Execute one lease end to end and report the result."""
    from mythril_tpu.observability import spans as obs
    from mythril_tpu.resilience.checkpoint import (
        drain_requested, get_checkpoint_plane,
    )

    payload = header["payload"]
    journal_dir = header["journal_dir"]
    if header.get("journal_wire"):
        # remote attach: the grant body IS the journal — materialize
        # it locally and run from there (no shared filesystem)
        from mythril_tpu.parallel import fabric as fabric_mod

        journal_dir = tempfile.mkdtemp(prefix="mtpu-wire-")
        fabric_mod.unpack_journal(body, journal_dir)
        session.wire_dir = journal_dir
    tracer = obs.get_tracer()
    if payload.get("trace"):
        tracer.enable()
        tracer.reset()
    # adopt the coordinator's trace identity: this worker's spans and
    # lane records belong to the same request timeline
    obs.set_trace_id(payload.get("trace_id"))
    from mythril_tpu.observability.ledger import set_origin

    set_origin(contract=payload.get("name"),
               scope=header.get("lease_id"),
               trace=payload.get("trace_id"))
    _worker_reset_scope(journal_dir, payload.get("args", {}))
    with session.lease_lock:
        session.lease_header = header
    began = time.time()
    error = None
    try:
        from mythril_tpu.analysis.symbolic import SymExecWrapper
        from mythril_tpu.laser.ethereum.time_handler import time_handler
        from mythril_tpu.solidity.evmcontract import EVMContract

        exec_timeout = payload.get("execution_timeout") or 86400
        time_handler.start_execution(exec_timeout)
        contract = EVMContract(
            code=payload["code"], name=payload.get("name", "contract")
        )
        SymExecWrapper(
            contract,
            address=payload["address"],
            strategy="bfs",
            max_depth=payload.get("max_depth") or 10 ** 9,
            execution_timeout=exec_timeout,
            create_timeout=payload.get("create_timeout") or 10,
            transaction_count=payload["transaction_count"],
            compulsory_statespace=False,
        )
    except Exception as exc:  # noqa: BLE001 — report, don't die: the
        #               coordinator decides between re-lease and fallback
        log.exception("worker: lease %s failed", header["lease_id"])
        error = f"{type(exc).__name__}: {exc}"
    finally:
        with session.lease_lock:
            session.lease_header = None
    if error is not None:
        session.send({
            "type": "error",
            "lease_id": header["lease_id"],
            "stamp": header["stamp"],
            "worker_id": session.worker_id,
            "message": error,
        })
        _worker_lease_cleanup(session)
        return
    from mythril_tpu.resilience.checkpoint import CheckpointPlane

    findings = CheckpointPlane._findings_snapshot()
    issues = [
        issue for per_module in findings["issues"].values()
        for issue in per_module
    ]
    partial = bool(
        drain_requested() or get_checkpoint_plane().partial
    )
    if partial:
        # a drained/split remote lease: the coordinator re-leases from
        # the boundary journal, which only exists on its side if we
        # ship it one last time before the result
        session.ship_checkpoint(header)
    from mythril_tpu.observability.ledger import get_ledger

    result_body = pickle.dumps({
        "findings": findings,
        "spans": tracer.events() if payload.get("trace") else None,
        # lane-ledger aggregates ride home with the result so the
        # coordinator's artifact covers the whole fleet (records stay
        # local — bounded memory on both sides)
        "ledger": get_ledger().snapshot(),
    }, protocol=4)
    session.send(
        {
            "type": "result",
            "lease_id": header["lease_id"],
            "stamp": header["stamp"],
            "worker_id": session.worker_id,
            "trace_id": payload.get("trace_id"),
            "partial": partial,
            "found_swcs": sorted(
                {i.swc_id for i in issues if i.swc_id}
            ),
            "wall_s": round(time.time() - began, 3),
        },
        result_body,
    )
    _worker_lease_cleanup(session)


#: sentinel from _worker_connect_once: the connection died in a way a
#: redial could fix (coordinator restart, network blip)
_RECONNECT = -1


def _worker_connect_once(host: str, port: int, worker_id: str,
                         secret: Optional[bytes]) -> int:
    """One connect → handshake → lease-serving session.  Returns an
    exit code, or :data:`_RECONNECT` when redialing makes sense."""
    global _worker_session
    from mythril_tpu.parallel import fabric as fabric_mod
    from mythril_tpu.resilience import checkpoint

    try:
        conn = socket.create_connection((host, port), timeout=30)
    except OSError as exc:
        log.warning("worker: connect to %s:%d failed: %s",
                    host, port, exc)
        return _RECONNECT
    conn.settimeout(None)
    try:
        channel = fabric_mod.client_handshake(conn, secret, worker_id)
    except fabric_mod.FleetAuthError as exc:
        # wrong secret will not fix itself — structured exit, the
        # PR-11 bad-configuration contract
        log.error("worker: authentication failed: %s", exc)
        print(f"myth worker: authentication failed: {exc}",
              file=sys.stderr)
        try:
            conn.close()
        except OSError:
            pass
        return 2
    except (fabric_mod.FrameError, OSError) as exc:
        log.warning("worker: handshake failed: %s", exc)
        try:
            conn.close()
        except OSError:
            pass
        return _RECONNECT
    session = _WorkerSession(worker_id, conn, channel=channel)
    _worker_session = session
    interval = {"s": 0.5}
    threading.Thread(target=session.reader_loop, name="fleet-reader",
                     daemon=True).start()
    threading.Thread(target=session.heartbeat_loop, args=(interval,),
                     name="fleet-heartbeat", daemon=True).start()
    while True:
        item = session.lease_queue.get()
        if item is None:
            return _RECONNECT if session.closed else 0
        if session.closed:
            return _RECONNECT
        header, body = item
        interval["s"] = float(header.get("heartbeat_s", 0.5))
        _worker_run_lease(session, header, body)
        if checkpoint._drain_event.is_set():
            # a signal drain is sticky by design (PR-3): this process
            # reported its partial result and must be replaced, not
            # reused with a poisoned drain flag
            return 0


def worker_main(argv: Optional[List[str]] = None) -> int:
    """Entry point of ``python -m mythril_tpu.parallel.fleet --worker``
    and of the ``myth worker`` CLI: connect (authenticated when a
    secret is configured), heartbeat, run leases until shutdown — and
    redial up to ``--reconnect`` times so a coordinator restart is a
    pause, not a death."""
    import argparse

    from mythril_tpu.parallel import fabric as fabric_mod
    from mythril_tpu.resilience import checkpoint
    from mythril_tpu.support.env import env_int

    parser = argparse.ArgumentParser()
    parser.add_argument("--worker", action="store_true")
    parser.add_argument("--connect", required=True)
    parser.add_argument("--id", required=True)
    parser.add_argument("--secret-file", default=None)
    parser.add_argument("--reconnect", type=int, default=None)
    opts = parser.parse_args(argv)
    host, _, port = opts.connect.rpartition(":")
    try:
        if opts.secret_file:
            secret = fabric_mod.load_secret(opts.secret_file)
        else:
            secret = fabric_mod.resolve_secret()
    except fabric_mod.FleetAuthError as exc:
        print(f"myth worker: {exc}", file=sys.stderr)
        return 2
    retries = (opts.reconnect if opts.reconnect is not None
               else env_int("MYTHRIL_TPU_FLEET_RECONNECT", 0, floor=0))
    checkpoint.install_signal_handlers()
    from mythril_tpu.ops import configure_jax

    configure_jax()
    # knowledge store: load once at seat start (warm leases from the
    # first one) — inert without MYTHRIL_TPU_PERSIST_DIR
    from mythril_tpu.persist.plane import get_knowledge_plane

    if get_knowledge_plane().active:
        get_knowledge_plane().store
    global _worker_session
    attempt = 0
    while True:
        _worker_session = None
        code = _worker_connect_once(host or "127.0.0.1", int(port),
                                    opts.id, secret)
        if code != _RECONNECT:
            return code
        if checkpoint._drain_event.is_set():
            return 0
        if _worker_session is not None:
            # an authenticated session was established and then lost
            # (coordinator restart): that is progress, not a dead
            # endpoint — the redial budget meters consecutive failures
            attempt = 0
        attempt += 1
        if attempt > retries:
            return 0
        time.sleep(min(5.0, 0.5 * attempt))


def reset_fleet_for_tests() -> None:
    global _worker_session
    _worker_session = None
    fleet_stats.reset()


if __name__ == "__main__":
    # ``python -m mythril_tpu.parallel.fleet`` executes this file as
    # ``__main__`` — a second module object.  Delegate to the CANONICAL
    # import so the session global lives where the svm seam (which
    # imports ``mythril_tpu.parallel.fleet``) will look for it.
    from mythril_tpu.parallel.fleet import worker_main as _canonical_main

    sys.exit(_canonical_main())
