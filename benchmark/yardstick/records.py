"""The program's own record of each analysis
(``mythril_tpu.observability.analysis_profiles``: wall, self seconds by
span, waits on the device, compile and prefetch seconds), paired with
the rows of the window.

The last ``len(run.rows)`` records are the window's, one per row in
order; the records before them are the warm-up's.  Nothing is read
where the program keeps no records, where it kept fewer than the
window's rows, or where a pair's walls differ by more than
max(0.05 s, 2%): the records would then not be those analyses.
"""

WALL_SLACK_S = 0.05
WALL_SLACK_SHARE = 0.02


def _all():
    try:
        from mythril_tpu.observability import analysis_profiles
    except ImportError:  # a program without the records
        return None
    return analysis_profiles()


def _split(run):
    records, n = _all(), len(run.rows)
    if records is None or not n or len(records) < n:
        return None
    window = records[len(records) - n:]
    for row, record in zip(run.rows, window):
        slack = max(WALL_SLACK_S, WALL_SLACK_SHARE * row["wall_s"])
        if abs(row["wall_s"] - record["wall_s"]) > slack:
            return None
    return records[:len(records) - n], window


def window(run):
    """The window's records, or None (see the module docstring)."""
    split = _split(run)
    return None if split is None else split[1]


def warmup(run):
    """The records before the window's, or None where there are none
    or the window's do not pair (see the module docstring)."""
    split = _split(run)
    return None if split is None or not split[0] else split[0]
