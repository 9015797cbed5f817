"""Device dispatch: seconds the analysis blocked on the device — a
result fetch, an upload that can queue behind a running kernel, the
step-cache lock it shares with the prefetch worker — the self seconds
of the program's ``device.wait`` spans.  Mean over the window."""

from statistics import mean

from yardstick import records


def read(run):
    window = records.window(run)
    if window is None:
        return None
    return mean(r["self_s"].get("device.wait", 0.0) for r in window)
