"""Kernels (``ops/resident.py``): device seconds of the resident solve
kernel, the XLA module ``jit_rounds``, summed from the profiler trace
of the traced round, per analysis of that round.  Nothing to read
without a trace or where the module never ran."""

MODULE = "jit_rounds"


def read(run):
    if run.trace is None or not run.traced_rows:
        return None
    seconds = [s for name, s in run.trace["module_s"].items()
               if name.startswith(MODULE)]
    if not seconds:
        return None
    return sum(seconds) / len(run.traced_rows)
