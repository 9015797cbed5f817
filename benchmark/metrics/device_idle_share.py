"""Device: the share of the traced round in which no operation ran on
the device (1 minus the union of device-op intervals over the traced
window, from the profiler trace)."""


def read(run):
    if run.trace is None:
        return None
    return run.trace["idle_share"]
