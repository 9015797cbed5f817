"""CLI and report: the wall of each analysis that no layer's span
covers — the self seconds of its root span (``cli.analyze``: argument
handling, contract loading, report rendering and printing).  Mean over
the window."""

from statistics import mean

from yardstick import records


def read(run):
    window = records.window(run)
    if window is None:
        return None
    return mean(r["self_s"].get(r["name"], 0.0) for r in window)
