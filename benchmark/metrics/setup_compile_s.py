"""Compile: seconds the warm-up's analyses spent compiling or loading
programs from the compile cache (``compile_s`` of the program's records
before the window's), summed."""

from yardstick import records


def read(run):
    warmup = records.warmup(run)
    if warmup is None:
        return None
    return sum(r["compile_s"] for r in warmup)
