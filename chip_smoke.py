#!/usr/bin/env python3
"""Bring-up smoke: ``myth analyze`` on the chip, through the CLI.

Everything runs in this one process (a chip belongs to one process at a
time), through ``mythril_tpu.interfaces.cli.main`` — the code the
``myth`` launcher calls — on the embedded contracts of ``bench.py``.

One chip (the default), in order:

- **device**: ``jax.devices()`` must be TPUs; there is no CPU fallback;
- **wide_frontier**: ``bench.scale_contract(depth=6)`` at ``-t 1
  --device-force-dispatch`` finds SWC-106 with device and resident
  dispatches;
- **default_path**: ``bench.batchtoken_contract()`` at ``-t 2`` in auto
  mode finds SWC-101;
- **parity**: both contracts again with ``--no-batched-solving`` (the
  serial reference semantics) give the same SWC ids and addresses;
- **warm_rerun**: the wide-frontier run again adds no entry to the
  persistent compile cache.

``--chips 4`` runs only the four-chip path and what it is compared
with: the wide frontier on the dp x cp mesh (``mesh_dispatches > 0``)
against the serial run, ``__graft_entry__.dryrun_multichip(4)`` with its
per-lane parity against the host CDCL, and the bytes each device holds.

Every phase prints one line.  ``unhealthy_skips``, ``cpu_auto_skips``,
``watchdog_trips`` and ``demotions`` must stay 0 in every device phase.
A failed phase exits nonzero before the last line, which is exactly
``{"ok": true, "device": {...}}``.
"""

import argparse
import contextlib
import io
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SILENT_COUNTERS = ("unhealthy_skips", "cpu_auto_skips", "watchdog_trips",
                   "demotions")
EXECUTION_TIMEOUT_S = 240


class PhaseFailed(RuntimeError):
    pass


def _check(ok: bool, message: str) -> None:
    if not ok:
        raise PhaseFailed(message)


def _cache_entries() -> set:
    """Names of the executables in the persistent compile cache (JAX
    writes one ``<module>-<hash>-cache`` file per entry)."""
    from mythril_tpu.ops import compile_cache_dir

    return {name for _, _, files in os.walk(compile_cache_dir())
            for name in files if name.endswith("-cache")}


def _fresh_state() -> None:
    """The slate a new ``myth analyze`` process starts from: solver
    context, model memo, detection-module caches, counters."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.observability import ledger
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.smt.solver import SolverStatistics, reset_blast_context
    from mythril_tpu.support.model import clear_model_cache

    reset_blast_context()  # resets the autopilot tuner too
    # the tuner steps the resident kernel's fan/period from the
    # ledger's tail share: a ledger carried over from the last run
    # steers it to knob values (kernel variants) a new process never
    # compiles
    ledger.reset_for_tests()
    clear_model_cache()
    SolverStatistics().reset()
    for module in ModuleLoader().get_detection_modules():
        module.reset_module()
        module.cache.clear()
    dispatch_stats.reset()  # resets the resilience counters too


def myth_analyze(code: str, tx_count: int, *flags: str) -> dict:
    """One ``myth analyze -o jsonv2`` run in this process; returns the
    findings as sorted (swc, address) pairs plus the run's counters."""
    from mythril_tpu.interfaces.cli import main
    from mythril_tpu.observability import analysis_profiles
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.smt.solver import SolverStatistics

    _fresh_state()
    argv = ["myth", "analyze", "-v", "3", "-c", code, "--bin-runtime",
            "-t", str(tx_count), "--no-onchain-data",
            "--execution-timeout", str(EXECUTION_TIMEOUT_S),
            "-o", "jsonv2", *flags]
    out = io.StringIO()
    saved_argv, sys.argv = sys.argv, argv
    began = time.monotonic()
    try:
        with contextlib.redirect_stdout(out):
            main()
    except SystemExit as exc:
        _check(exc.code in (None, 0), f"myth analyze exited {exc.code}")
    finally:
        sys.argv = saved_argv
    wall = time.monotonic() - began
    report = json.loads(out.getvalue().strip().splitlines()[-1])[0]
    meta = report.get("meta", {})
    errors = [log["msg"] for log in meta.get("logs", [])
              if log.get("level") == "error"]
    _check(not errors, f"analysis raised: {errors[:1]}")
    _check(not meta.get("resilience", {}).get("partial"),
           "analysis returned a partial report")
    findings = sorted(
        (issue["swcID"], int(issue["locations"][0]["sourceMap"].split(":")[0]))
        for issue in report["issues"]
    )
    stats = dispatch_stats.as_dict()
    counters = {key: stats[key] for key in (
        "dispatches", "resident_dispatches", "mesh_dispatches",
        "device_dispatch_calls", "lanes", "fused", *SILENT_COUNTERS,
    )}
    # where the wall went: the solver's own split, device dispatch
    # time, and the spans with the most self time in the analysis's
    # record (the same spans --trace-out would record), with its waits
    # on the device and its compile seconds
    split = dict(SolverStatistics().split(), device_s=stats["device_s"])
    split["other_s"] = wall - sum(split.values())
    record = analysis_profiles()[-1]
    heavy = sorted(record["self_s"].items(), key=lambda item: -item[1])[:6]
    return {"wall_s": wall, "findings": findings, "counters": counters,
            "split_s": split, "self_s": dict(heavy),
            "wait_s": record["wait_s"], "compile_s": record["compile_s"]}


def _expect(run: dict, swc: str, *, device: bool) -> None:
    ids = {finding[0] for finding in run["findings"]}
    _check(swc in ids, f"{swc} not found (found {sorted(ids)}) in "
           f"{run['wall_s']:.1f} s, counters {run['counters']}")
    if device:
        loud = {k: run["counters"][k] for k in SILENT_COUNTERS
                if run["counters"][k]}
        _check(not loud, f"the device was skipped or demoted: {loud}")


def _phase(name: str, fn, *args) -> dict:
    before = _cache_entries()
    began = time.monotonic()
    result = fn(*args)
    added = sorted(_cache_entries() - before)
    result = dict(result, phase_wall_s=time.monotonic() - began,
                  cache_entries_before=len(before),
                  cache_entries_added=len(added))
    print(f"phase {name}: {json.dumps(result, default=str)}", flush=True)
    return dict(result, new_cache_entries=added)


def _device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {json.dumps(info)}", flush=True)
    _check(info["platform"] == "tpu",
           f"no TPU: JAX runs on {info['platform']!r}")
    _check(info["count"] >= chips,
           f"{chips} chips asked for, {info['count']} present")
    return info


def _wide_frontier(bench, kernel: str = "resident_dispatches") -> dict:
    run = myth_analyze(bench.scale_contract(depth=6), 1,
                       "--device-force-dispatch")
    _expect(run, "SWC-106", device=True)
    _check(run["counters"]["dispatches"] > 0, "no device dispatch")
    _check(run["counters"][kernel] > 0, f"no {kernel}")
    return run


def _default_path(bench) -> dict:
    run = myth_analyze(bench.batchtoken_contract(), 2)
    _expect(run, "SWC-101", device=True)
    return run


def _parity(bench, device_runs: dict) -> dict:
    out = {}
    for name, (code, tx_count) in {
        "scale": (bench.scale_contract(depth=6), 1),
        "batchtoken": (bench.batchtoken_contract(), 2),
    }.items():
        serial = myth_analyze(code, tx_count, "--no-batched-solving")
        _check(serial["findings"] == device_runs[name]["findings"],
               f"{name}: device {device_runs[name]['findings']} != "
               f"serial {serial['findings']}")
        out[name] = serial
    return out


def _warm_rerun(bench) -> dict:
    return _wide_frontier(bench)


def _mesh_frontier(bench) -> dict:
    run = _wide_frontier(bench, kernel="mesh_dispatches")
    serial = myth_analyze(bench.scale_contract(depth=6), 1,
                          "--no-batched-solving")
    _check(serial["findings"] == run["findings"],
           f"mesh {run['findings']} != serial {serial['findings']}")
    return dict(run, serial_findings=serial["findings"])


def _dryrun_multichip() -> dict:
    import __graft_entry__

    __graft_entry__.dryrun_multichip(4)  # asserts per-lane parity
    return {"parity": "per-lane verdicts agree with the host CDCL"}


def _device_bytes(held: list) -> dict:
    import jax

    _check(bool(held), "no mesh dispatch placed data")
    stats = [device.memory_stats() or {} for device in jax.devices()]
    in_use = [s.get("bytes_in_use", 0) for s in stats]
    peak = [s.get("peak_bytes_in_use", 0) for s in stats]
    _check(all(in_use), f"a device holds no data: {in_use}")
    _check(in_use[0] < sum(in_use), "device 0 holds everything")
    return {"bytes_in_use": in_use, "peak_bytes_in_use": peak}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip mesh path")
    opts = parser.parse_args()
    sys.path.insert(0, REPO)
    try:
        import bench
        from mythril_tpu.ops import configure_jax
    except ImportError as exc:
        print(f"chip_smoke: the repository is not here ({exc})",
              file=sys.stderr)
        return 2
    try:
        device = _device(opts.chips)
        configure_jax()
        print(f"compile cache: {os.environ.get('JAX_COMPILATION_CACHE_DIR') or 'default'}"
              f" ({len(_cache_entries())} entries)", flush=True)
        if opts.chips == 1:
            runs = {
                "scale": _phase("wide_frontier", _wide_frontier, bench),
                "batchtoken": _phase("default_path", _default_path, bench),
            }
            _phase("parity", _parity, bench, runs)
            warm = _phase("warm_rerun", _warm_rerun, bench)
            _check(warm["findings"] == runs["scale"]["findings"],
                   "warm rerun changed the findings")
            _check(not warm["new_cache_entries"],
                   "warm rerun added compile-cache entries: "
                   f"{warm['new_cache_entries'][:8]}")
        else:
            from mythril_tpu.parallel import mesh

            held = []
            place = mesh.place_on_mesh

            def keep_last(*args):
                # hold the last mesh dispatch's shards so the bytes
                # phase reads them where the mesh path put them
                held[:] = place(*args)
                return held

            mesh.place_on_mesh = keep_last
            _phase("mesh_frontier", _mesh_frontier, bench)
            _phase("dryrun_multichip", _dryrun_multichip)
            _phase("device_bytes", _device_bytes, held)
    except PhaseFailed as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
