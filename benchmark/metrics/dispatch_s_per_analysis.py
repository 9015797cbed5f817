"""Device dispatch (``ops/batched_sat.py`` ``batch_check``): host-clock
seconds inside device dispatches — cone, build, solve and fetch
(``dispatch_stats.device_s``).  Mean over the window."""

from statistics import mean


def read(run):
    if not run.rows:
        return None
    return mean(row["counters"]["device_s"] for row in run.rows)
