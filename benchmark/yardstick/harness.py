"""One run of one cell: warm-up, the measured window, the comparison
with the reference, and the result line.

The system under test comes in through three callables (``reset``,
``analyze``, ``device``) and the profiler through ``tracer``, so the
tests drive the same loop with stubs and with faults planted.
"""

import contextlib
import json
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from yardstick import stats
from yardstick.reference import expected_findings, mismatches
from yardstick.traffic import Traffic

P90 = 0.9


@dataclass
class Run:
    """What the per-layer readers of ``benchmark/metrics/`` read."""

    rows: List[dict]
    traced_rows: List[dict] = field(default_factory=list)
    trace: Optional[dict] = None
    compiles_in_window: int = 0


class NoTracer:
    def annotate(self, name: str):
        return contextlib.nullcontext()


def measure(cell, seed: int, seconds: float, trace: bool, *, reset: Callable,
            analyze: Callable, device: Callable, tracer=None,
            compiles=None, started: float, clock=time.monotonic,
            log=sys.stderr):
    """Returns the result object of the run (the benchmark's last line)
    and the rows of the window."""
    config = cell.config
    tracer = tracer or NoTracer()
    traffic = Traffic(cell.traffic, seed)

    def one(contract) -> dict:
        with tracer.annotate("bench.reset"):
            reset()
        with tracer.annotate(f"bench.analysis.{contract.family}"):
            row = analyze(contract.hex)
        row["contract"] = contract
        return row

    for contract in traffic.round(-1):
        warm = one(contract)
        print(f"warm-up {contract.family}: {warm['wall_s']:.3f} s", file=log)
    setup_s = clock() - started
    if compiles is not None:
        compiles.start()

    run = Run(rows=[])
    began = clock()
    for index, contracts in enumerate(traffic.rounds()):
        traced = trace and index == 0
        if traced:
            tracer.start()
        with tracer.annotate("bench.traced") if traced else contextlib.nullcontext():
            for contract in contracts:
                if run.rows and clock() - began >= seconds:
                    break
                run.rows.append(one(contract))
        if traced:
            run.trace = tracer.stop()
            run.traced_rows = list(run.rows)
        if clock() - began >= seconds:
            break
    window_s = clock() - began
    if compiles is not None:
        run.compiles_in_window = compiles.stop()

    info = device()
    checks = _compare(run.rows, config, log)
    walls = [row["wall_s"] for row in run.rows]
    print(f"window {window_s:.3f} s, {len(walls)} analyses, "
          f"p90 by nearest rank of {len(walls)} walls, "
          f"{run.compiles_in_window} compiles in the window", file=log)
    if run.trace is not None:
        summary = {key: run.trace[key] for key in
                   ("window_s", "busy_s", "chips", "module_s")}
        print(f"trace of {len(run.traced_rows)} analyses: "
              f"{json.dumps(summary)}", file=log)

    if trace:
        metrics = {}
        for metric in cell.per_layer:
            value = cell.reader(metric["name"])(run)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        if run.trace is not None:
            info = dict(info, busy_s=run.trace["busy_s"],
                        window_s=run.trace["window_s"])
    else:
        values = {"analyses_per_min": stats.per_minute(len(walls), window_s),
                  "analysis_p90_s": stats.nearest_rank(walls, P90),
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    failed = sum(1 for row in run.rows if row["verdict"] != "ok")
    result = {
        "correct": bool(run.rows) and all(
            c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(run.rows),
        "failed": failed,
        "metrics": metrics,
        "device": info,
    }
    if trace and run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace["top_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["compared"] = checks
    return result, run


def _compare(rows: List[dict], config: dict, log) -> dict:
    """Every analysis of the window against the reference.  Sets each
    row's verdict and returns the numbers compared with their limits."""
    limits = config["limits"]
    totals = {name: 0 for name in limits}
    for row in rows:
        contract = row["contract"]
        expected = expected_findings(contract, config["tx_count"])
        missed = mismatches(expected, row["findings"])
        off = [key for key in config.get("off_device_counters", ())
               if row["counters"][key]]
        totals["finding_mismatches"] += missed
        totals["broken_analyses"] += bool(row["errors"])
        if "off_device_analyses" in totals:
            totals["off_device_analyses"] += bool(off)
        row["verdict"] = "ok" if not (missed or off or row["errors"]) else (
            f"findings {sorted(row['findings'])} expected {sorted(expected)}; "
            f"off device {off}; errors {[e[-300:] for e in row['errors']]}")
        counters = {k: v for k, v in row["counters"].items() if v}
        print(f"analysis {contract.family} {row['wall_s']:.3f} s "
              f"findings {row['findings']} {row['verdict']} {counters} "
              f"queries {row['queries']}", file=log)
    return {name: {"value": totals[name], "limit": limits[name]}
            for name in limits}


def emit(result: dict, out=sys.stdout, log=sys.stderr) -> None:
    """The numbers compared go last on standard error; the result is
    the last line of standard output."""
    for name, check in result["compared"].items():
        print(f"compared {name} {check['value']} limit {check['limit']}",
              file=log)
    log.flush()
    print(json.dumps(result), file=out, flush=True)
