"""Native CDCL (``native/``): host seconds in the native solver
(``SolverStatistics`` ``native_s``).  Mean over the window."""

from statistics import mean


def read(run):
    if not run.rows:
        return None
    return mean(row["solver_s"]["native_s"] for row in run.rows)
