"""Drives the system under test: one ``myth analyze`` in this process,
through ``mythril_tpu.interfaces.cli.main``, from the slate a new
``myth analyze`` process starts from.

A copy of ``chip_smoke.py:64-139``, which is the sound part of what
the repo had.  It returns one row per analysis: the wall on the host
clock around the whole call (the report is printed, so every device
result is on the host), the findings of the ``-o jsonv2`` report as
(SWC id, address), the device counters, the solver's host seconds and
the query count.
"""

import contextlib
import io
import json
import sys
import time
import traceback

COUNTERS = (
    "dispatches", "resident_dispatches", "mesh_dispatches",
    "device_dispatch_calls", "lanes", "unsat", "sat_verified", "undecided",
    "profit_skips", "device_s",
)
# the ways off the device that a run would not otherwise show; the
# frontiers the slow-dispatch fuse skipped are counted by FuseWatch
# (``fused`` says only that it tripped, perhaps after the last frontier)
OFF_DEVICE = ("fused", "unhealthy_skips", "cpu_auto_skips",
              "watchdog_trips", "demotions")
SOLVER_SECONDS = ("probe_s", "blast_s", "cone_s", "native_s")


class FuseWatch:
    """Counts the frontiers that the slow-dispatch fuse kept off the
    device in one analysis.  The program's own count (the backend's
    ``fused_skips``) restarts at 0 at each new solver context and when
    a retry that decided lanes re-arms the fuse, so it is read after
    every frontier, through the lane ledger's batch observer, and each
    rise is added up.  Every skip happens inside one frontier's
    ``batch_check_states`` call, and the ledger closes that call's batch
    after it, so no skip falls between two readings."""

    def __init__(self):
        from mythril_tpu.observability import ledger

        self.skips = 0
        self.frontiers = 0
        self._last = self._read()
        ledger.add_batch_observer(self._on_batch)

    @staticmethod
    def _read():
        from mythril_tpu.ops.batched_sat import get_backend

        backend = get_backend()
        return backend.futile_ctx_generation, backend.fused_skips

    def start(self) -> None:
        self.skips = self.frontiers = 0
        self._last = self._read()

    def _on_batch(self, batch) -> None:
        generation, skips = now = self._read()
        last_generation, last = self._last
        restarted = generation != last_generation or skips < last
        self.skips += skips if restarted else skips - last
        self.frontiers += 1
        self._last = now


_watch = None


def configure() -> None:
    from mythril_tpu.ops import configure_jax

    global _watch
    configure_jax()
    _watch = FuseWatch()


def fresh_state() -> None:
    """Solver context, autopilot tuner, lane ledger, model memo,
    detection-module caches and counters, as a new process has them.
    The tuner steps the resident kernel's knobs from the ledger's tail
    share: carried over, they would make the window compile kernel
    variants a new process never builds."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.observability import ledger
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.smt.solver import SolverStatistics, reset_blast_context
    from mythril_tpu.support.model import clear_model_cache

    reset_blast_context()
    ledger.reset_for_tests()
    clear_model_cache()
    SolverStatistics().reset()
    for module in ModuleLoader().get_detection_modules():
        module.reset_module()
        module.cache.clear()
    dispatch_stats.reset()


def analyze(code_hex: str, tx_count: int, flags, execution_timeout: int) -> dict:
    from mythril_tpu.interfaces.cli import main
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.smt.solver import SolverStatistics

    argv = ["myth", "analyze", "-v", "3", "-c", code_hex, "--bin-runtime",
            "-t", str(tx_count), "--no-onchain-data",
            "--execution-timeout", str(execution_timeout),
            "-o", "jsonv2", *flags]
    out = io.StringIO()
    errors = []
    _watch.start()
    saved_argv, sys.argv = sys.argv, argv
    began = time.monotonic()
    try:
        with contextlib.redirect_stdout(out):
            main()
    except SystemExit as exc:
        if exc.code not in (None, 0):
            errors.append(f"myth analyze exited {exc.code}")
    except Exception:  # the window goes on; the analysis counts as failed
        errors.append(traceback.format_exc(limit=4))
    finally:
        sys.argv = saved_argv
    wall = time.monotonic() - began

    findings = []
    try:
        report = json.loads(out.getvalue().strip().splitlines()[-1])[0]
        meta = report.get("meta", {})
        errors += [log["msg"] for log in meta.get("logs", [])
                   if log.get("level") == "error"]
        if meta.get("resilience", {}).get("partial"):
            errors.append("partial report")
        findings = sorted(
            (issue["swcID"],
             int(issue["locations"][0]["sourceMap"].split(":")[0]))
            for issue in report["issues"])
    except (IndexError, KeyError, ValueError) as exc:
        errors.append(f"no readable report: {exc!r}")

    stats = dispatch_stats.as_dict()
    if stats["dispatches"] and not _watch.frontiers:
        errors.append("the lane ledger reported no frontier: fuse skips unread")
    solver = SolverStatistics()
    return {
        "wall_s": wall,
        "findings": findings,
        "errors": errors,
        "counters": dict({key: stats[key] for key in COUNTERS + OFF_DEVICE},
                         fused_skips=_watch.skips),
        "solver_s": {key: getattr(solver, key) for key in SOLVER_SECONDS},
        "queries": solver.query_count,
    }
