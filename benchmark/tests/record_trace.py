"""Records the small profiler trace that ``test_trace.py`` reads.  Run
it on the chip (``python3 benchmark/tests/record_trace.py <dir>``): a
jitted ``rounds`` loop twice inside ``bench.analysis.fixture`` with a
50 ms host-only sleep between, then a 20 ms ``bench.reset``, all inside
``bench.traced``.  It writes ``<dir>/trace_fixture.xplane.pb`` and
prints the planes and lines of the trace."""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation


@jax.jit
def rounds(x):
    return jax.lax.fori_loop(0, 20, lambda i, y: jnp.tanh(y @ y), x)


def main() -> int:
    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    x = jnp.ones((512, 512), jnp.float32)
    rounds(x).block_until_ready()
    directory = tempfile.mkdtemp(prefix="bench-fixture-")
    jax.profiler.start_trace(directory)
    with TraceAnnotation("bench.traced"):
        with TraceAnnotation("bench.analysis.fixture"):
            rounds(x).block_until_ready()
            time.sleep(0.05)
            rounds(x).block_until_ready()
        with TraceAnnotation("bench.reset"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)[0]
    fixture = os.path.join(out, "trace_fixture.xplane.pb")
    shutil.copy(path, fixture)
    shutil.rmtree(directory)
    for plane in ProfileData.from_file(fixture).planes:
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})[:6]
            print(f"{plane.name} | {line.name} | {len(events)} | {names}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
