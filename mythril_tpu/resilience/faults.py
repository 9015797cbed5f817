"""Deterministic fault-injection plane.

Every partial-failure seam in the system consults this plane through a
named injection point, so every degradation path — watchdog trip,
retry, context/process demotion, RPC backoff — is testable
on a CPU-only host with no real hardware failing.

Injection points:

==================  =====================================================
``dispatch_hang``    a device dispatch blocks for ``hang_s`` seconds and
                     then dies (models a kernel the runtime never
                     returns from; the watchdog must trip first)
``dispatch_error``   a device dispatch raises (stands in for
                     ``XlaRuntimeError`` — the retry rung's territory)
``dispatch_garbage`` a device dispatch returns corrupted lanes: every
                     status flips to "SAT candidate" with a garbage
                     assignment, which host-side model verification must
                     reject (validates the safety net on the candidate
                     path — device UNSAT soundness is a kernel contract,
                     not something garbage can silently forge into
                     findings)
``probe_flap``       the device verdict flips healthy → demoted mid-run
                     (``device_ok()`` starts answering False)
``cdcl_error``       the native CDCL raises on solve (the authoritative
                     tail's own retry rung)
``prefetch_error``   the async prefetch worker raises mid-flight (the
                     batch must be dropped, never decided)
``rpc_error``        the RPC transport raises a transient ``OSError``
``rpc_http_500``     the RPC transport answers HTTP 500
``lane_poison``      a device dispatch raises ONLY while a designated
                     lane is in the dispatched batch (models a
                     lane-dependent kernel abort: one query's data
                     wedges the kernel while its siblings are fine) —
                     the poisoned-lane bisection's territory
``frontier_stall``   a frontier-tier round (adjacency-gather BCP +
                     first-UIP learning, ops/frontier.py) raises
                     before launching — the event-driven dispatch
                     shape walks the same retry/bisect/demote ladder
                     as dense rounds, and the chaos suite pins that
                     findings survive it
``serve_crash``      the analysis of a served request raises unhandled
                     mid-execution (models a poisoned contract whose
                     exploration crashes the executor) — the serve
                     engine's request-isolation territory: that request
                     fails with a flight dump, the pool is
                     decontaminated, the server stays ready
``worker_kill``      a fleet worker SIGKILLs itself at a transaction
                     boundary (models spot-instance preemption mid-
                     lease) — the coordinator must detect the death by
                     heartbeat expiry and re-lease the subtree from the
                     worker's last journal boundary
``gossip_drop``      the coordinator silently drops one knowledge
                     gossip message (models a lossy channel) — findings
                     must be unaffected: gossip is an accelerant, never
                     load-bearing
``governor_breach``  one governor poll observes a resource-budget
                     breach (whatever the real counters say) — the
                     degradation rung ladder's chaos hook
``rpc_flap``         the provider pool's current provider drops the
                     connection mid-call — rotation + breaker coverage
``rpc_code_cache``   one on-disk code-cache read answers as a miss —
                     the loader must fall through to the network
``lease_partition``  the coordinator ignores one worker heartbeat
                     (models a network partition): enough shots expire
                     the lease, the subtree is re-leased under a bumped
                     epoch, and the original worker becomes the zombie
                     whose stale-epoch messages the fence must drop
==================  =====================================================

Faults are armed either through the API (:meth:`FaultPlane.arm`) or the
environment::

    MYTHRIL_TPU_FAULT="dispatch_hang:3:1,rpc_error,lane_poison:9:0:2"

Each comma-separated spec is ``point[:times[:skip[:lane]]]`` — fire
``times`` shots (default 1) after letting ``skip`` clean hits through
(default 0, so ``skip`` is how a fault lands *mid*-analysis instead of
on the first dispatch); ``lane`` designates the poisoned lane for
``lane_poison``.  A malformed spec (typo'd point name, non-integer
field) raises :class:`FaultSpecError` — a chaos run configured to
inject nothing must die at startup, not pass vacuously.
``MYTHRIL_TPU_FAULT_HANG_S`` sets the hang duration (default 30 s —
far past any test deadline, so an untripped watchdog is a loud
failure, not a flake).

Firing is deterministic: a shot is consumed per hit of the point, under
a lock, with no randomness — the same schedule fires the same faults in
the same order on every run.

Kill-resume hook: ``MYTHRIL_TPU_KILL_AT="point[:skip]"`` SIGKILLs the
process the moment the named injection point is *reached* (after
``skip`` clean hits), whether or not a fault is armed there — the
checkpoint/resume chaos driver (``scripts/chaos_corpus.py
--kill-resume``) uses it to die at every seam and prove the journal
restores identical findings.
"""

import logging
import os
import threading
from typing import Dict, Optional

import numpy as np

from mythril_tpu.resilience.telemetry import resilience_stats

log = logging.getLogger(__name__)

FAULT_POINTS = (
    "dispatch_hang",
    "dispatch_error",
    "dispatch_garbage",
    "probe_flap",
    "cdcl_error",
    "prefetch_error",
    "rpc_error",
    "rpc_http_500",
    "lane_poison",
    "frontier_stall",
    "serve_crash",
    "worker_kill",
    "gossip_drop",
    "lease_partition",
    "remote_auth_fail",
    "frame_corrupt",
    # knowledge store (persist/store.py): fires inside flush(), before
    # the segment write — an armed shot aborts the flush (records stay
    # staged), MYTHRIL_TPU_KILL_AT lands a SIGKILL mid-flush
    "persist_flush",
    # resource governor (resilience/governor.py): an armed shot makes
    # one poll() observe a breach regardless of the real budgets — the
    # degradation rung ladder is testable without exhausting anything
    "governor_breach",
    # provider pool (ethereum/interface/rpc/client.py): a transient
    # per-provider connection drop mid-call — the pool must rotate to
    # the next provider and the breaker must count the failure
    "rpc_flap",
    # on-disk code cache (pool.eth_getCode): an armed shot makes one
    # cache read answer as a miss (models a quarantined segment) — the
    # loader must fall through to the network, never crash
    "rpc_code_cache",
    # veritesting tier (laser/ethereum/veritest.py): an armed shot
    # aborts one state merge mid-join — the pair degrades to plain
    # forking (both lanes survive), findings parity must hold
    "merge_abort",
)

DEFAULT_HANG_S = 30.0


class FaultInjected(RuntimeError):
    """Raised by an armed error fault (stands in for XlaRuntimeError,
    a native-solver abort, or a dropped socket, depending on the
    injection point)."""


class FaultSpecError(ValueError):
    """A malformed ``MYTHRIL_TPU_FAULT`` / ``MYTHRIL_TPU_KILL_AT``
    spec.  Raised at plane construction so a chaos run whose schedule
    would silently inject nothing dies at startup instead."""


class FaultPlane:
    """Armed fault shots, keyed by injection point."""

    def __init__(self):
        self._lock = threading.Lock()
        self._armed: Dict[str, dict] = {}
        self.fired: Dict[str, int] = {}
        self.hits: Dict[str, int] = {}
        self._kill_at: Optional[str] = None
        self._kill_skip = 0
        self._load_env()

    # -- arming --------------------------------------------------------

    def arm(self, point: str, times: int = 1, skip: int = 0,
            hang_s: Optional[float] = None,
            lane: Optional[int] = None) -> None:
        """Arm ``times`` shots of ``point``, skipping the first ``skip``
        hits (a skip is how a fault lands mid-run).  ``lane`` names the
        poisoned lane for ``lane_poison`` — the shot only fires (and
        only counts a hit) while that lane is in the dispatched
        batch."""
        if point not in FAULT_POINTS:
            raise FaultSpecError(
                f"unknown fault point {point!r} (choose from {FAULT_POINTS})"
            )
        if point == "lane_poison" and lane is None:
            raise FaultSpecError(
                "lane_poison needs a lane (arm(..., lane=K) or the "
                "fourth spec field: lane_poison:times:skip:K)"
            )
        with self._lock:
            self._armed[point] = {
                "times": times, "skip": skip, "hang_s": hang_s,
                "lane": lane,
            }

    def clear(self) -> None:
        with self._lock:
            self._armed.clear()
            self.fired.clear()
            self.hits.clear()

    def _load_env(self) -> None:
        spec = os.environ.get("MYTHRIL_TPU_FAULT", "").strip()
        for part in spec.split(",") if spec else ():
            fields = part.strip().split(":")
            if not fields[0]:
                continue
            try:
                self.arm(
                    fields[0],
                    times=int(fields[1]) if len(fields) > 1 else 1,
                    skip=int(fields[2]) if len(fields) > 2 else 0,
                    lane=int(fields[3]) if len(fields) > 3 else None,
                )
            except FaultSpecError:
                raise
            except (ValueError, IndexError) as exc:
                raise FaultSpecError(
                    f"bad MYTHRIL_TPU_FAULT spec {part!r}: {exc}"
                ) from exc
        kill = os.environ.get("MYTHRIL_TPU_KILL_AT", "").strip()
        if kill:
            fields = kill.split(":")
            if fields[0] not in FAULT_POINTS:
                raise FaultSpecError(
                    f"MYTHRIL_TPU_KILL_AT names unknown point "
                    f"{fields[0]!r} (choose from {FAULT_POINTS})"
                )
            try:
                self._kill_skip = int(fields[1]) if len(fields) > 1 else 0
            except ValueError as exc:
                raise FaultSpecError(
                    f"bad MYTHRIL_TPU_KILL_AT spec {kill!r}: {exc}"
                ) from exc
            self._kill_at = fields[0]

    # -- firing --------------------------------------------------------

    def fire(self, point: str, lane_ids=None) -> Optional[dict]:
        """Consume one hit of ``point``.  Returns the armed spec when a
        shot fires, None when the point is unarmed or the hit was a
        configured skip.  The caller applies the effect.  For
        ``lane_poison``, a hit only registers while the armed lane is
        present in ``lane_ids`` — absence neither fires nor consumes.
        The kill-at hook (see module docstring) triggers on hits of its
        named point regardless of what is armed."""
        kill_now = False
        with self._lock:
            spec = self._armed.get(point)
            if spec is not None and spec.get("lane") is not None and (
                lane_ids is None or spec["lane"] not in lane_ids
            ):
                spec = None
            self.hits[point] = self.hits.get(point, 0) + 1
            if point == self._kill_at:
                if self._kill_skip > 0:
                    self._kill_skip -= 1
                else:
                    kill_now = True
            if spec is not None:
                if spec["skip"] > 0:
                    spec["skip"] -= 1
                    spec = None
                elif spec["times"] <= 0:
                    spec = None
                else:
                    spec["times"] -= 1
                    self.fired[point] = self.fired.get(point, 0) + 1
                    resilience_stats.faults_fired += 1
        if kill_now:
            log.warning("fault plane: SIGKILL at injection point %s "
                        "(MYTHRIL_TPU_KILL_AT)", point)
            logging.shutdown()
            os.kill(os.getpid(), 9)  # SIGKILL: no cleanup, by design
        if spec is not None:
            from mythril_tpu.observability import spans as obs

            obs.instant("fault.fired", cat="resilience", point=point)
            log.info("fault plane: firing %s", point)
        return spec


_plane: Optional[FaultPlane] = None


def get_fault_plane() -> FaultPlane:
    global _plane
    if _plane is None:
        _plane = FaultPlane()
    return _plane


def reset_for_tests() -> None:
    global _plane
    _plane = None


# ---------------------------------------------------------------------------
# Seam helpers: each injection point's effect, applied where it fires
# ---------------------------------------------------------------------------


def _hang_s(spec: dict) -> float:
    if spec.get("hang_s") is not None:
        return float(spec["hang_s"])
    return float(os.environ.get("MYTHRIL_TPU_FAULT_HANG_S", DEFAULT_HANG_S))


def maybe_fault_dispatch(lane_ids=None) -> None:
    """Device-dispatch seam: called inside the watchdog-supervised
    thunk, so a hang is tripped by the deadline and an error lands in
    the retry rung.  A hang sleeps and then RAISES (never falls through
    to the real dispatch): a real wedge parks the worker inside the
    runtime forever, so the worker resuming and racing the host would
    be an artifact of injection, not a behavior to simulate.

    ``lane_ids`` names the lanes riding this dispatch (the round
    ladder's global batch positions): an armed ``lane_poison`` raises
    only while its lane is aboard, which is what lets the bisection
    isolate it."""
    plane = get_fault_plane()
    spec = plane.fire("dispatch_hang")
    if spec is not None:
        import time

        time.sleep(_hang_s(spec))
        raise FaultInjected("injected dispatch hang expired")
    if plane.fire("dispatch_error") is not None:
        raise FaultInjected(
            "injected XlaRuntimeError: device dispatch failed"
        )
    if plane.fire("lane_poison", lane_ids=lane_ids) is not None:
        raise FaultInjected(
            "injected lane-dependent kernel abort (poisoned lane aboard)"
        )


def maybe_abort_merge() -> bool:
    """Veritesting merge seam (laser/ethereum/veritest.py): True when
    an armed ``merge_abort`` shot fires, which aborts the in-flight
    state merge AFTER eligibility passed — both lanes survive and fork
    on, the degraded path whose findings parity the chaos soak pins."""
    return get_fault_plane().fire("merge_abort") is not None


def maybe_fault_frontier() -> None:
    """Frontier-round seam (ops/batched_sat._dispatch_round, frontier
    mode): fires inside the watchdog-supervised thunk, so an injected
    stall walks the retry → bisect → demote ladder exactly like a
    dense-round failure — the chaos coverage for the event-driven
    dispatch path."""
    if get_fault_plane().fire("frontier_stall") is not None:
        raise FaultInjected("injected frontier-round stall")


def maybe_corrupt_lanes(status: np.ndarray, assign: np.ndarray):
    """Garbage-lane seam: when ``dispatch_garbage`` fires, every lane
    claims a complete SAT candidate over a garbage assignment.  Host
    model verification must reject them (lanes fall to the CDCL tail);
    any other outcome is a detection-oracle failure the chaos tests
    catch."""
    if get_fault_plane().fire("dispatch_garbage") is None:
        return status, assign
    status = np.ones_like(status)
    garbage = np.ones_like(assign)
    garbage[..., ::2] = -1
    return status, garbage


def health_flap() -> bool:
    """Device-verdict seam: True when ``probe_flap`` fires — the caller
    (ops/device_health.py) demotes the process."""
    return get_fault_plane().fire("probe_flap") is not None


def maybe_fault_cdcl() -> None:
    """Native-CDCL seam (smt/bitblast.py check): raises when armed."""
    if get_fault_plane().fire("cdcl_error") is not None:
        raise FaultInjected("injected native CDCL abort")


def maybe_fault_prefetch() -> None:
    """Async-prefetch seam (ops/async_dispatch.py worker)."""
    if get_fault_plane().fire("prefetch_error") is not None:
        raise FaultInjected("injected prefetch worker failure")


def maybe_fault_request() -> None:
    """Served-request seam (serve/engine.py, fired from inside the
    analysis execution scope): raises when ``serve_crash`` is armed, so
    chaos tests can crash exactly one request and assert the isolation
    contract — flight dump attached, breaker decremented, resident pool
    decontaminated, the NEXT request's findings untouched."""
    if get_fault_plane().fire("serve_crash") is not None:
        raise FaultInjected("injected served-request crash")


def maybe_fault_worker_kill() -> None:
    """Fleet-worker seam (parallel/fleet.py, fired at each transaction
    boundary of a lease): SIGKILL this process when armed — the
    preemption the coordinator's heartbeat detector and journal
    re-lease exist to absorb.  Same no-cleanup semantics as the
    MYTHRIL_TPU_KILL_AT hook: a preempted worker gets no goodbyes."""
    if get_fault_plane().fire("worker_kill") is not None:
        log.warning("fault plane: fleet worker self-SIGKILL "
                    "(worker_kill)")
        logging.shutdown()
        os.kill(os.getpid(), 9)


def maybe_fault_rpc() -> None:
    """RPC-transport seam: raises the same exception types the real
    transport does, so the injected failure walks the client's own
    classification and retry path."""
    if get_fault_plane().fire("rpc_error") is not None:
        raise OSError("injected connection reset")
    if get_fault_plane().fire("rpc_http_500") is not None:
        import urllib.error

        raise urllib.error.HTTPError(
            "http://injected", 500, "injected server error", None, None
        )


def maybe_fault_rpc_flap() -> None:
    """Provider-pool seam (pool._call, per provider attempt): a
    transient connection drop against the CURRENT provider — the pool
    must rotate and the per-provider breaker must count it."""
    if get_fault_plane().fire("rpc_flap") is not None:
        raise OSError("injected provider flap")


def maybe_fault_governor() -> bool:
    """Governor seam (governor.poll): True when ``governor_breach``
    fires — that poll observes a breach and applies the next rung."""
    return get_fault_plane().fire("governor_breach") is not None


def maybe_fault_code_cache() -> bool:
    """Code-cache seam (pool.eth_getCode cache read): True when
    ``rpc_code_cache`` fires — the read answers as a miss and the
    loader falls through to the network."""
    return get_fault_plane().fire("rpc_code_cache") is not None
