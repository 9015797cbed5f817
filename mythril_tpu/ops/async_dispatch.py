"""Asynchronous device dispatch: overlap accelerator solving with host
exploration (VERDICT r3 #1 / SURVEY §7 north star).

The synchronous dispatch path (ops/batched_sat.py) must beat the CPU
on wall-clock to be worth blocking for, so its profit gate keeps the
device idle whenever the CDCL clears the residue faster — correct, and
exactly why BENCH_r03 showed zero device seconds.  This module changes
the economics: when the profit gate declines a frontier, the same
prepared batch can be launched WITHOUT blocking (jax dispatch is
asynchronous; the host thread returns before the kernel finishes) and
harvested on a later call once the arrays are ready.  The device then
only has to beat *idle time*:

- device-refuted lanes land in the UNSAT memo and as pool nogoods, so
  when the frontier re-presents the same (or a superset) constraint
  set — frontiers repeat sets round over round — the host skips the
  CDCL work entirely;
- device models that verify against the terms enter ``recent_models``,
  feeding the word-level probe the same way CDCL models do.

The solve runs as a ladder of short rounds (the sync gather ladder's
64-step round, ``BatchedSatBackend._prefetch_rounds``), and a
synchronous device wait preempts the prefetch at its next round: XLA
cannot cancel a running kernel, so without the cut a sync caller would
queue behind the whole solve.  Every ``device.wait`` span entered on
another thread than the worker requests the cut, and so does a new
blast-context generation (the batch's analysis is over); the lanes
decided by then are harvested as usual, the rest stay undecided, as
after a budget bail (telemetry: async_preempted, async_rounds).  A
pending batch whose results never arrive before the analysis ends is
simply dropped (telemetry: async_dropped).
"""

import logging
import threading
import time
from typing import List, Optional

import numpy as np

from mythril_tpu.observability import spans as obs

log = logging.getLogger(__name__)


class AsyncStats:
    def __init__(self):
        self.reset()

    def reset(self):
        self.launches = 0          # batches launched without blocking
        self.harvested = 0         # batches whose results were consumed
        self.unsat = 0             # lanes refuted (memoized + nogood)
        self.models = 0            # device models verified + remembered
        self.dropped = 0           # pending batches discarded unread
        self.launch_s = 0.0        # host time spent launching (non-block)
        self.harvest_s = 0.0       # host time spent harvesting
        self.rounds = 0            # solve rounds run by the worker
        self.preempted = 0         # batches cut between two rounds

    def as_dict(self):
        return {f"async_{k}": v for k, v in self.__dict__.items()}


async_stats = AsyncStats()


class AsyncDispatcher:
    """One in-flight batch at a time, tied to a blast-context
    generation.  The caller is ops/batched_sat.batch_check_states:
    ``harvest`` runs at every entry (cheap readiness check), ``launch``
    runs when the profit gate declines a frontier the device could
    still prefetch."""

    def __init__(self):
        self.pending = None
        # the worker of the last launch, tracked INDEPENDENTLY of
        # pending: a dropped batch must still be joinable at exit, or
        # finalization kills the thread mid-XLA (abort, exit 134)
        self._live_thread = None
        # the batch that worker runs, for the same reason: a dropped
        # batch's rounds can still be cut
        self._live_batch = None

    # -- preemption ----------------------------------------------------

    def preempt(self) -> None:
        """Ask the running worker to stop at its next round boundary;
        a no-op on the worker's own thread and when none runs.  The
        ``device.wait`` hook (see :meth:`launch`)."""
        batch = self._live_batch
        if (batch is not None and not batch["done"]
                and batch["worker"] != threading.get_ident()):
            batch["preempt"] = True

    @staticmethod
    def _cut(batch) -> bool:
        from mythril_tpu.smt.solver import live_generation

        return (batch["preempt"]
                or live_generation() != batch["generation"])

    # -- launch --------------------------------------------------------

    def launch(self, backend, ctx, rep_assumption_sets, rep_node_sets,
               rep_constraint_sets) -> bool:
        """Prepare (on this thread — the only part that touches the
        blast context) and hand the compile+launch to a worker thread,
        so even a first-per-bucket jit compile never blocks the host.
        Returns True when a batch went in flight."""
        if self.pending is not None:
            return False
        if self._live_thread is not None and self._live_thread.is_alive():
            # a dropped batch's worker is still inside the device stack:
            # never run two kernels' worth of prefetch concurrently
            return False
        began = time.monotonic()
        runner = backend.prepare_gather(ctx, rep_assumption_sets)
        if runner is None:
            return False
        pending = {
            "generation": ctx.generation,
            "status": None,
            "assign": None,
            "done": False,
            "preempt": False,
            "worker": None,
            "began": time.monotonic(),
            "assumption_sets": list(rep_assumption_sets),
            "node_sets": list(rep_node_sets),
            "constraint_sets": list(rep_constraint_sets),
        }

        # the launching analysis is charged the seconds its prefetch is
        # in flight, also where the kernel ends after the analysis did;
        # the worker's own spans stay out of every analysis record
        record = obs.current_analysis()
        if record is not None:
            record.prefetch_launched()

        def work():
            pending["worker"] = threading.get_ident()
            try:
                from mythril_tpu.resilience import faults

                cut = False
                try:
                    with obs.span("prefetch.launch", cat="prefetch"):
                        faults.maybe_fault_prefetch()
                        rounds = runner()
                        handle = next(rounds)
                    while True:
                        # block on the worker, never on the host, and
                        # round by round, so a cut lands between two
                        # rounds: done=True only once no round runs
                        with obs.span("prefetch.wait", cat="prefetch"):
                            handle["status"].block_until_ready()
                            status = np.asarray(handle["status"])
                        async_stats.rounds += 1
                        if handle["last"] or (status != 0).all():
                            break  # no lane has a step left to take
                        if self._cut(pending):
                            cut = True
                            break
                        with obs.span("prefetch.launch", cat="prefetch"):
                            handle = next(rounds)
                finally:
                    async_stats.preempted += cut
                    if record is not None:
                        record.prefetch_done(preempted=cut)
                # a decision-stack bail (3) is undecided, as in the
                # one-shot solve
                pending["status"] = np.where(status == 3, 0, status)
                assign = np.asarray(handle["assign"])
                if "cone_vars" in handle:
                    # cone-tier runner: expand the compact assignment
                    # back to full var space on the worker thread so
                    # harvest's _env_from_assignment works unchanged
                    cone_vars = handle["cone_vars"]
                    full = np.zeros(
                        (assign.shape[0], handle["full_width"]), np.int8
                    )
                    full[:, cone_vars] = assign[:, 1:cone_vars.size + 1]
                    assign = full
                pending["assign"] = assign
            except Exception as exc:  # noqa: BLE001 — prefetch only
                log.debug("async dispatch failed: %s", exc)
                pending["failed"] = True
            pending["done"] = True

        # a synchronous device wait on any other thread cuts the batch
        obs.set_wait_hook(self.preempt)
        self._live_batch = pending
        thread = threading.Thread(target=work, daemon=True)
        thread.start()
        self._live_thread = thread
        _register_shutdown_join()
        self.pending = pending
        async_stats.launches += 1
        async_stats.launch_s += time.monotonic() - began
        return True

    # -- harvest -------------------------------------------------------

    def _ready(self) -> bool:
        # the worker blocks until the kernel finished before setting
        # done, so readiness is just the flag
        return bool(self.pending["done"])

    def harvest(self, ctx) -> None:
        """Consume a finished batch, if any (one cut short: the lanes
        its rounds decided).  Never blocks: a batch still in flight
        stays pending; a batch from a dead context is dropped."""
        if self.pending is None:
            return
        if self.pending["generation"] != ctx.generation:
            self.pending = None
            async_stats.dropped += 1
            return
        if self.pending.get("failed"):
            self.pending = None
            async_stats.dropped += 1
            return
        if not self._ready():
            # prefetch watchdog: a batch in flight past the dispatch
            # deadline cap means the kernel hung.
            # Abandon it — the worker stays parked inside the runtime
            # (it blocks future launches via _live_thread, which is the
            # degraded state: the prefetch channel goes dark, sync
            # solving is untouched) and its lanes are simply never
            # memoized, so nothing is lost but the idle-time win.
            import os

            deadline = float(
                os.environ.get("MYTHRIL_TPU_DISPATCH_TIMEOUT", "120")
            )
            if time.monotonic() - self.pending["began"] > deadline:
                from mythril_tpu.resilience.telemetry import resilience_stats

                resilience_stats.watchdog_trips += 1
                resilience_stats.demotions += 1
                log.warning(
                    "async prefetch exceeded the %.0fs dispatch deadline; "
                    "abandoning the batch (prefetch channel demoted)",
                    deadline,
                )
                self.pending = None
                async_stats.dropped += 1
            return
        began = time.monotonic()
        pending, self.pending = self.pending, None
        from mythril_tpu.smt import terms as T

        with obs.wait("prefetch.harvest"):
            status = np.asarray(pending["status"])
            assign = np.asarray(pending["assign"])
        from mythril_tpu.ops.batched_sat import _env_from_assignment

        from mythril_tpu.support.support_args import args as _args

        proof_log = getattr(_args, "proof_log", False)
        for lane, node_set in enumerate(pending["node_sets"]):
            if status[lane] == 2:
                if proof_log:
                    # the memo/nogood channel ships UNSAT verdicts that
                    # later queries consume WITHOUT a fresh solve, so a
                    # certificate must exist first: a small host solve
                    # records the ASSUMPTION_CONFLICT event (this is an
                    # opportunistic prefetch — an unconfirmed lane is
                    # simply dropped, never decided)
                    if not ctx.confirm_unsat(
                        pending["assumption_sets"][lane],
                        conflict_budget=1000,
                    ):
                        continue
                # sound UNSAT: permanent memo + pool nogood, so the
                # CDCL and later dispatches inherit the refutation
                ctx.note_unsat(node_set)
                ctx.learn_nogood(
                    pending["assumption_sets"][lane], certified=proof_log
                )
                async_stats.unsat += 1
            elif status[lane] == 1:
                env = _env_from_assignment(ctx, assign[lane])
                ok = True
                for constraint in pending["constraint_sets"][lane]:
                    node = getattr(constraint, "raw", constraint)
                    if isinstance(node, bool):
                        continue
                    if T.evaluate(node, env) is not True:
                        ok = False
                        break
                if ok:
                    # tag with the device truth row so harvested models
                    # seed later dispatches' warm starts too
                    ctx._remember_model(env, truth=assign[lane])
                    async_stats.models += 1
        async_stats.harvested += 1
        async_stats.harvest_s += time.monotonic() - began

    def drop(self) -> None:
        if self.pending is not None:
            self.pending = None
            async_stats.dropped += 1


_shutdown_join_registered = False


def join_pending_at_exit() -> None:
    """Join the in-flight worker with a BOUNDED deadline.  The old
    unbounded-ish 60 s join meant a dispatch wedged at exit stalled
    process teardown for a full minute per process (a corpus driver
    fans out many); now the deadline is `MYTHRIL_TPU_SHUTDOWN_JOIN_S`
    (default 10 s) and an abandoned dispatch is logged by name so the
    stall is attributable.  The daemon worker then dies with the
    process — the same teardown we'd have had, a minute sooner."""
    import os

    dispatcher = _dispatcher
    if dispatcher is None:
        return
    thread = dispatcher._live_thread
    if thread is None or not thread.is_alive():
        return
    try:
        deadline = float(
            os.environ.get("MYTHRIL_TPU_SHUTDOWN_JOIN_S", "10")
        )
    except ValueError:
        deadline = 10.0
    thread.join(timeout=deadline)
    if thread.is_alive():
        log.warning(
            "abandoning in-flight async dispatch %r at exit "
            "(did not finish within %.1fs)", thread.name, deadline,
        )


def _register_shutdown_join() -> None:
    """CPython finalization kills daemon threads at arbitrary points;
    a worker torn down inside XLA's C++ aborts the whole process
    (observed: exit 134, 'FATAL: exception not rethrown').  Join the
    in-flight worker at exit, bounded (see join_pending_at_exit)."""
    global _shutdown_join_registered
    if _shutdown_join_registered:
        return
    _shutdown_join_registered = True
    import atexit

    atexit.register(join_pending_at_exit)


_dispatcher: Optional[AsyncDispatcher] = None


def get_async_dispatcher() -> AsyncDispatcher:
    global _dispatcher
    if _dispatcher is None:
        _dispatcher = AsyncDispatcher()
    return _dispatcher
