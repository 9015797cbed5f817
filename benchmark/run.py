#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In this one process, which holds the chip: warm-up (one round of the
cell's traffic, so every device shape the window uses is compiled or
loaded from the checkout's ``.jax_cache``), then a closed loop of
``myth analyze`` calls over the cell's contracts for ``--seconds``, then
the comparison of every analysis with the plain reference.  The last
line of standard output is the result object; the last lines of
standard error are the numbers compared, each with its limit.

``--variant`` runs a check of the configuration's own (its control, or
a planted fault) in place of the plain configuration; the driver never
passes it.  Exits 2 and prints no result where JAX finds no TPU, or
fewer chips than the cell asks for.
"""

import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from yardstick import cells  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Backend compiles (an XLA compile or a persistent-cache load)
    that ``jax.monitoring`` reports between ``start`` and ``stop``."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.active and event == BACKEND_COMPILE:
            self.count += 1

    def start(self):
        self.active = True

    def stop(self) -> int:
        self.active = False
        return self.count


def _device_info(jax) -> dict:
    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--variant", default=None,
                        help="a check named under the configuration's "
                        "'variants' (control, planted fault)")
    opts = parser.parse_args()

    cell = cells.resolve(opts.workload)
    config = cell.config
    flags = list(config["flags"])
    if opts.variant:
        variant = config["variants"][opts.variant]
        flags += variant.get("flags", [])
        os.environ.update(variant.get("env", {}))
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cells.ROOT,
                                                           ".jax_cache")
    sys.path.append(cells.ROOT)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {cell.chips} TPU chip(s) needed, JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2

    from yardstick import harness, myth, trace

    myth.configure()
    result, _ = harness.measure(
        cell, opts.seed, opts.seconds, bool(opts.trace),
        reset=myth.fresh_state,
        analyze=lambda code: myth.analyze(
            code, config["tx_count"], flags, config["execution_timeout_s"]),
        device=lambda: _device_info(jax),
        tracer=trace.Profiler() if opts.trace else None,
        compiles=CompileCounter(),
        started=STARTED,
    )
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
