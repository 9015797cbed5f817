"""Solve funnel (``laser/batch.py``, ``smt/``): solver queries an
analysis makes (``SolverStatistics.query_count``).  Mean over the
window."""

from statistics import mean


def read(run):
    if not run.rows:
        return None
    return mean(row["queries"] for row in run.rows)
