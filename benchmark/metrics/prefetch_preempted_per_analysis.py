"""Device dispatch (async prefetch): prefetches an analysis launched
that were cut between two rounds, by a synchronous device wait or a
new solver context, counted on that analysis even where the cut comes
after it (``prefetch_preempted`` of the program's record).  Mean over
the window; nothing where the records do not count it."""

from statistics import mean

from yardstick import records


def read(run):
    window = records.window(run)
    if window is None or any("prefetch_preempted" not in r for r in window):
        return None
    return mean(r["prefetch_preempted"] for r in window)
