"""Device dispatch (async prefetch): seconds from the launch of each
prefetch an analysis started to its kernel's completion, credited to
that analysis even where the kernel ends after it
(``prefetch_inflight_s`` of the program's record).  Mean over the
window."""

from statistics import mean

from yardstick import records


def read(run):
    window = records.window(run)
    if window is None:
        return None
    return mean(r["prefetch_inflight_s"] for r in window)
