"""Kernels: iterations of the resident kernel's loop an analysis, summed
over its dispatches (``resident_iterations`` of the program's record,
the ``itc`` each dispatch returns).  The search's length, so device
seconds of ``jit_rounds`` over it is the cost of one iteration.  Mean
over the window; nothing where the records do not count it."""

from statistics import mean

from yardstick import records


def read(run):
    window = records.window(run)
    if window is None or any("resident_iterations" not in r
                             for r in window):
        return None
    return mean(r["resident_iterations"] for r in window)
