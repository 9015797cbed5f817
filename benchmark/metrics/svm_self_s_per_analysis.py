"""Symbolic VM (``laser/``): seconds inside the VM's own spans
(``svm.*``: transaction, round, segment, merge, subsume) and none of
their children, from the program's record of each analysis.  Mean over
the window."""

from statistics import mean

from yardstick import records


def read(run):
    window = records.window(run)
    if window is None:
        return None
    return mean(sum(s for name, s in r["self_s"].items()
                    if name.startswith("svm."))
                for r in window)
