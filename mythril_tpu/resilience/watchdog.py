"""Per-dispatch watchdog and the escalation ladder.

A dispatch that hangs in ``block_until_ready`` or raises must not take
the whole analysis with it (the zero-decision fuse in
ops/batched_sat.py only catches dispatches that return).  This module
bounds every device dispatch:

- the dispatch thunk runs on a supervised worker thread joined with a
  **deadline derived from the dispatch's own observed latency EWMA**
  (``min(cap, max(floor, ewma * mult))``, cap =
  ``MYTHRIL_TPU_DISPATCH_TIMEOUT``); a cold key (first dispatch of a
  shape — jit compile dominates) gets the full cap;
- a tripped deadline or a raised dispatch walks the **escalation
  ladder**: bounded retry with exponential backoff + jitter → demote
  this analysis context to the native CDCL tail (the caller's job,
  signaled by :class:`DispatchAbandoned`) → demote the whole process
  when the failing dispatch's own error says the device is gone
  (:func:`device_lost`; ``device_health.mark_unhealthy`` then routes
  every later device path through ``unhealthy_skips``).  Nothing is
  re-probed from a child process: the process that holds the chip is
  the only one that can reach it.

Lanes in flight on an abandoned dispatch are returned as undecided, so
the caller's CDCL tail re-solves them — no frontier state is ever
dropped and findings are identical to the fault-free run; only the
batching speedup is lost.

A tripped worker is left parked on purpose: it is stuck inside the
runtime and dies with the process.  Cooperative code that the worker
would run *after* the runtime returns (host-side chunk loops that touch
the blast context) must call :func:`raise_if_cancelled` between chunks
so an abandoned worker can never race the host on shared native state.

Between the retry rung and context demotion sits the poisoned-lane
bisection (ops/batched_sat.py): a repeatably failing round-ladder
dispatch is bisected over the lane buckets and only the offending
lane(s) are quarantined to the CDCL tail — the context stays on
device.  This module exposes the rungs separately for it:
:meth:`DispatchWatchdog.run_attempts` (retry rung, raises
:class:`DispatchFailed`) and :meth:`DispatchWatchdog.give_up`
(demotion accounting + :class:`DispatchAbandoned`).

Env knobs:
  MYTHRIL_TPU_DISPATCH_TIMEOUT   deadline cap in seconds (default 120;
                                 first compile of a shape can be slow)
  MYTHRIL_TPU_DISPATCH_RETRIES   ladder retries per dispatch (default 2)
  MYTHRIL_TPU_DISPATCH_BACKOFF_S retry backoff base (default 0.05)
  MYTHRIL_TPU_EWMA_CAP           latency-table entry cap (default 64,
                                 LRU eviction like the probe memo)
"""

import logging
import os
import random
import threading
import time
from typing import Callable, Dict, Optional

from mythril_tpu.observability import flight as obs_flight
from mythril_tpu.observability import spans as obs
from mythril_tpu.resilience.telemetry import resilience_stats

log = logging.getLogger(__name__)

DEADLINE_FLOOR_S = 5.0   # warm deadlines never drop below this
DEADLINE_MULT = 8.0      # deadline = EWMA x this (dispatch latency has
#                          heavy tails: pool refresh, cache miss)
EWMA_ALPHA = 0.3
# latency-table entry cap: round-ladder keys ("gather:64", "cone:512",
# "frontier:64" — the event-driven frontier rounds budget their own
# deadline model instead of inheriting stale dense-round EWMAs)
# multiply the key space per bucket, and a long soak over many pool
# shapes would otherwise grow the table without bound.  LRU like
# PROBE_MEMO_CAP: hits refresh recency, the stale quarter is evicted.
# The resident solver deliberately collapses its key space to ONE
# family per lane bucket ("resident:8", "resident:64") — a persistent
# dispatch has no per-round budget axis, so keying on one would be
# pure table pressure; the bucket is the only latency-relevant shape.
EWMA_CAP = 64


def ewma_cap() -> int:
    """Effective latency-table cap: ``MYTHRIL_TPU_EWMA_CAP`` when set,
    floored so the eviction quarter never rounds to zero."""
    try:
        return max(8, int(os.environ.get("MYTHRIL_TPU_EWMA_CAP",
                                         EWMA_CAP)))
    except ValueError:
        return EWMA_CAP


#: jax's compile phases (trace, lower, backend compile).  jax opens each
#: with a scalar event carrying its start time and closes it with a
#: duration event, both on the compiling thread.
COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))


class _CompileClock:
    """Seconds each thread has spent compiling, from ``jax.monitoring``.
    A supervised dispatch's compile is set-up, not a hung device: the
    watchdog extends the deadline by it and keeps it out of the EWMA,
    so the first dispatch of a new shape never trips a warm deadline
    (on a v5e a resident-kernel recompile tripped the 16 s deadline
    its 2 s warm dispatches had earned — PR 21).  The slow-dispatch
    fuse (ops/batched_sat.py) nets the process-wide :meth:`total` out
    of its dispatch wall for the same reason: a cold compile fused the
    device off after one dispatch on a v5e (PR 21)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._depth: Dict[int, int] = {}
        self._since: Dict[int, float] = {}
        self._spent: Dict[int, float] = {}
        self._closed = 0.0  # finished compile seconds, every thread

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_duration_secs_listener(self._on_end)

    def _on_start(self, event, value, **kwargs) -> None:
        if event not in COMPILE_EVENTS:
            return
        tid = threading.get_ident()
        with self._lock:
            depth = self._depth.get(tid, 0)
            if not depth:
                self._since[tid] = time.monotonic()
            self._depth[tid] = depth + 1

    def _on_end(self, event, duration, **kwargs) -> None:
        if event not in COMPILE_EVENTS:
            return
        tid = threading.get_ident()
        with self._lock:
            depth = self._depth.get(tid, 0) - 1
            if depth < 0:
                return
            self._depth[tid] = depth
            if depth:
                return
            took = time.monotonic() - self._since.pop(tid)
            self._spent[tid] = self._spent.get(tid, 0.0) + took
            self._closed += took
        # the compile is a child of whatever span it ran in: the open
        # analysis record counts it as device.compile seconds
        obs.complete_child("device.compile", took)

    def seconds(self, tid: int) -> float:
        """Compile seconds of thread ``tid`` so far, open phase included."""
        with self._lock:
            spent = self._spent.get(tid, 0.0)
            if self._depth.get(tid):
                spent += time.monotonic() - self._since[tid]
            return spent

    def total(self) -> float:
        """Compile seconds of every thread so far, open phases included."""
        now = time.monotonic()
        with self._lock:
            return self._closed + sum(
                now - since for since in self._since.values()
            )

    def forget(self, tid: int) -> None:
        with self._lock:
            for table in (self._depth, self._since, self._spent):
                table.pop(tid, None)


_compile_clock: Optional[_CompileClock] = None


def compile_clock() -> _CompileClock:
    global _compile_clock
    if _compile_clock is None:
        clock = _CompileClock()
        clock.install()
        _compile_clock = clock
    return _compile_clock


class WatchdogTimeout(RuntimeError):
    """A supervised dispatch exceeded its deadline."""


class DispatchFailed(RuntimeError):
    """The retry rung exhausted its attempts for one dispatch.  Raised
    by :meth:`DispatchWatchdog.run_attempts` WITHOUT demoting anything:
    the caller decides whether to escalate (``give_up`` — the classic
    context demotion) or to bisect the batch for a poisoned lane
    (ops/batched_sat.py)."""

    def __init__(self, message: str, last: Optional[BaseException] = None):
        super().__init__(message)
        self.last = last


class WatchdogCancelled(RuntimeError):
    """Raised inside an abandoned worker at its next cancellation
    checkpoint (see :func:`raise_if_cancelled`)."""


class DispatchAbandoned(RuntimeError):
    """The escalation ladder gave up on this dispatch: the caller must
    demote its context and leave every lane to the CDCL tail."""

    def __init__(self, message: str, process_demoted: bool = False):
        super().__init__(message)
        self.process_demoted = process_demoted


_tls = threading.local()


def raise_if_cancelled() -> None:
    """Cooperative cancellation checkpoint for supervised thunks.

    Host-side stages inside a supervised dispatch (per-chunk cone
    remaps etc.) call this before touching shared context state; after
    the watchdog abandons the dispatch the next checkpoint raises, so a
    late-waking worker can never race the host on the native pool."""
    event = getattr(_tls, "cancel_event", None)
    if event is not None and event.is_set():
        raise WatchdogCancelled("dispatch abandoned by watchdog")


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


#: status codes of a runtime error that mean the device itself is gone
#: (a halted or lost chip), not that one program or shape failed
DEVICE_LOST_CODES = ("UNAVAILABLE", "DATA_LOSS", "ABORTED")


def device_lost(exc: Optional[BaseException]) -> bool:
    """True when a dispatch's own error says the device is gone: an
    XLA runtime status that names a lost device.  A deadline miss, a
    compile error or an out-of-memory stay local to their context."""
    if exc is None or isinstance(exc, WatchdogTimeout):
        return False
    return str(exc).split(":", 1)[0].strip() in DEVICE_LOST_CODES


class DispatchWatchdog:
    """Deadline supervision + the escalation ladder, with a per-key
    latency EWMA (keys name dispatch shapes: 'gather', 'cone', 'mesh',
    'pallas' — their latency regimes differ by orders of magnitude)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ewma: Dict[str, float] = {}

    # -- deadline model ------------------------------------------------

    def deadline_for(self, key: str) -> float:
        cap = _env_f("MYTHRIL_TPU_DISPATCH_TIMEOUT", 120.0)
        with self._lock:
            ewma = self._ewma.get(key)
            if ewma is not None:
                # refresh recency (dict preserves insertion order): a
                # shape still dispatching must never be the one evicted
                del self._ewma[key]
                self._ewma[key] = ewma
        if ewma is None:
            return cap  # cold key: jit compile dominates, grant the cap
        return min(cap, max(DEADLINE_FLOOR_S, ewma * DEADLINE_MULT))

    def observe(self, key: str, elapsed_s: float) -> None:
        with self._lock:
            prev = self._ewma.pop(key, None)
            if prev is None:
                cap = ewma_cap()
                if len(self._ewma) >= cap:
                    # bounded like the probe memo: round-ladder keys
                    # ("gather:64" x pool buckets) grow the table per
                    # shape — drop the least-recently-used quarter
                    for stale in list(self._ewma)[: cap // 4]:
                        del self._ewma[stale]
            self._ewma[key] = (
                elapsed_s if prev is None
                else prev + EWMA_ALPHA * (elapsed_s - prev)
            )

    # -- one supervised attempt ----------------------------------------

    def run(self, key: str, thunk: Callable):
        """One attempt of ``thunk`` on a worker thread, joined with the
        key's deadline plus whatever time the worker spends compiling.
        Success records the latency net of compiles; a deadline miss
        raises :class:`WatchdogTimeout` (the worker is left parked and
        flagged cancelled); a thunk exception re-raises here."""
        deadline = self.deadline_for(key)
        clock = compile_clock()
        cancel = threading.Event()
        box: dict = {}
        # this thread only waits for the worker: the worker's spans nest
        # under this thread's open span and count in its analysis
        loan = obs.lend()

        def work():
            obs.borrow(loan)
            _tls.cancel_event = cancel
            try:
                box["result"] = thunk()
            except BaseException as exc:  # noqa: BLE001 — re-raised on host
                box["error"] = exc

        thread = threading.Thread(
            target=work, daemon=True, name=f"dispatch-watchdog-{key}"
        )
        began = time.monotonic()
        thread.start()
        while True:
            compiling = clock.seconds(thread.ident)
            remaining = began + deadline + compiling - time.monotonic()
            if remaining <= 0:
                break
            thread.join(remaining)
            if not thread.is_alive():
                break
        compiling = clock.seconds(thread.ident)
        clock.forget(thread.ident)
        if loan is not None:
            loan.revoke()
        if thread.is_alive():
            cancel.set()
            raise WatchdogTimeout(
                f"{key} dispatch exceeded its {deadline:.1f}s deadline"
            )
        if "error" in box:
            raise box["error"]
        self.observe(key, time.monotonic() - began - compiling)
        return box["result"]

    # -- the escalation ladder -----------------------------------------

    def run_attempts(self, key: str, thunk: Callable,
                     retries: Optional[int] = None):
        """The retry rung alone: bounded attempts with exponential
        backoff + jitter.  Returns the thunk's result or raises
        :class:`DispatchFailed` — no re-probe, no demotion accounting,
        so callers with a cheaper recovery (poisoned-lane bisection)
        can try it before escalating via :meth:`give_up`."""
        if retries is None:
            retries = int(_env_f("MYTHRIL_TPU_DISPATCH_RETRIES", 2))
        backoff = _env_f("MYTHRIL_TPU_DISPATCH_BACKOFF_S", 0.05)
        last: Optional[BaseException] = None
        for attempt in range(retries + 1):
            if attempt:
                resilience_stats.dispatch_retries += 1
                # exponential backoff + jitter: a transient runtime
                # error gets air between attempts, and concurrent
                # analyzer processes don't re-dispatch in lockstep
                time.sleep(
                    backoff * (2 ** (attempt - 1)) * (1 + random.random())
                )
            try:
                return self.run(key, thunk)
            except WatchdogTimeout as exc:
                resilience_stats.watchdog_trips += 1
                # timeline + post-mortem: the trip lands as an instant
                # event and the flight ring is dumped so the spans
                # leading up to the hang survive the retry/demotion
                obs.instant("watchdog.trip", cat="resilience", key=key,
                            attempt=attempt + 1)
                obs_flight.get_flight_recorder().dump("watchdog_trip")
                last = exc
                log.warning("%s (attempt %d/%d)", exc, attempt + 1,
                            retries + 1)
            except WatchdogCancelled:
                raise  # only ever raised inside workers, never here
            except Exception as exc:  # noqa: BLE001 — device/runtime error
                last = exc
                log.warning(
                    "%s dispatch raised (%s: %s) (attempt %d/%d)",
                    key, type(exc).__name__, exc, attempt + 1, retries + 1,
                )
        raise DispatchFailed(
            f"{key} dispatch failed after {retries + 1} attempts ({last})",
            last=last,
        )

    def give_up(self, key: str, last: Optional[BaseException]):
        """Terminal escalation for a dispatch nothing could recover:
        process demotion when its own error says the device is gone,
        demotion accounting, a checkpoint nudge (a degrading run is
        exactly the run about to be preempted), and
        :class:`DispatchAbandoned` for the caller's context demotion."""
        process_demoted = device_lost(last)
        if process_demoted:
            from mythril_tpu.ops.device_health import mark_unhealthy

            mark_unhealthy(f"{key} dispatch failed: {last}")
        resilience_stats.demotions += 1
        obs.instant("ladder.demotion", cat="resilience", key=key,
                    process_demoted=process_demoted)
        obs_flight.get_flight_recorder().dump("demotion")
        from mythril_tpu.resilience.checkpoint import get_checkpoint_plane

        get_checkpoint_plane().note_demotion()
        raise DispatchAbandoned(
            f"{key} dispatch abandoned ({last})",
            process_demoted=process_demoted,
        )

    def supervised(self, key: str, thunk: Callable):
        """Run ``thunk`` under the full ladder; returns its result or
        raises :class:`DispatchAbandoned` after every rung failed."""
        try:
            return self.run_attempts(key, thunk)
        except DispatchFailed as exc:
            self.give_up(key, exc.last)


_watchdog: Optional[DispatchWatchdog] = None


def get_watchdog() -> DispatchWatchdog:
    global _watchdog
    if _watchdog is None:
        _watchdog = DispatchWatchdog()
    return _watchdog


def reset_for_tests() -> None:
    global _watchdog
    _watchdog = None
