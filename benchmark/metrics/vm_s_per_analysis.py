"""Symbolic VM (``laser/``): the seconds of an analysis that are neither
the solver's host seconds (``SolverStatistics`` probe, blast, cone,
native) nor device dispatch (``dispatch_stats.device_s``) — what
``chip_smoke.py`` called ``other_s``.  Mean over the window."""

from statistics import mean


def read(run):
    if not run.rows:
        return None
    return mean(row["wall_s"] - sum(row["solver_s"].values())
                - row["counters"]["device_s"] for row in run.rows)
