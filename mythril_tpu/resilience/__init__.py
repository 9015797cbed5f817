"""Resilience subsystem: fault injection, dispatch supervision, and
degradation telemetry.

A device dispatch can hang in ``block_until_ready`` or raise mid-run,
and the analysis must survive it with unchanged findings.  This
package provides:

- :mod:`faults` — a deterministic, env/API-configurable fault plane
  with named injection points at every partial-failure seam (device
  dispatch, device verdict, native CDCL, async prefetch, RPC transport),
  so every degradation path is testable on a CPU-only host;
- :mod:`watchdog` — per-dispatch deadlines derived from the dispatch's
  own observed latency EWMA, plus the escalation ladder a tripped
  deadline walks (bounded retry with backoff → context demotion →
  process demotion when the dispatch's own error says the device is
  gone);
- :mod:`checkpoint` — the durable checkpoint/resume plane (atomic
  CRC-checked journal under ``--checkpoint-dir``, transaction-boundary
  frontier snapshots, periodic channel refresh, ``--resume``) plus the
  graceful-drain flag SIGTERM/SIGINT set and every long loop polls;
- :mod:`budget` — per-request wall-clock deadline budgets (the serve
  plane): an expired budget reads as a drain through the same
  cooperative seam, so one request winds down at a transaction
  boundary with a partial report while the process stays healthy;
- :mod:`telemetry` — the counters (``watchdog_trips``,
  ``dispatch_retries``, ``demotions``, ``quarantined_lanes``,
  ``bisect_dispatches``, ``checkpoints_written``, ``resumes``,
  ``rpc_retries``, ``faults_fired``) threaded through the dispatch
  stats, the bench headline, and the jsonv2 report.

Design rule shared by every consumer: degradation never changes
*results*, only who computes them — a demoted analysis re-solves every
in-flight lane on the native CDCL tail, a quarantined lane is re-solved
there alone (the context stays on device), and a killed-and-resumed
analysis rebuilds its frontier from the journal — findings are
identical to the fault-free, uninterrupted run in every case; only
speedup is lost.
"""

from mythril_tpu.resilience.telemetry import resilience_stats  # noqa: F401
