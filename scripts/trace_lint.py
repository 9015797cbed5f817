"""Artifact linter for the observability plane.

Validates the two machine-readable artifacts an analysis can emit:

- ``--trace-out`` against the Chrome ``trace_event`` schema subset this
  repo produces (object form with ``traceEvents``; every event carries
  ``name``/``ph``/``pid``/``tid``, a numeric ``ts`` for timed phases,
  a non-negative ``dur`` on complete events, and a known phase letter);
- ``--lane-ledger-out`` against the published
  ``mythril-tpu-lane-ledger/2`` schema (the ``/1`` reader is kept —
  old recordings stay lintable): required fields, tier-transition
  legality per record (observability/ledger.py ``LEGAL_NEXT``), the
  lane-conservation invariant — every opened lane terminates in exactly
  one tier, so ``lanes_total == sum(decided.values())`` — and, on v2
  records, the shape of the autopilot's ``features``/``routed_by``
  annotations (mythril_tpu/autopilot).

Usage::

    python scripts/trace_lint.py --trace TRACE.json
    python scripts/trace_lint.py --ledger LEDGER.json
    python scripts/trace_lint.py --selftest   # generate + lint both
                                              # (wired into tox)

Exit status: 0 = clean, 1 = findings (printed one per line), 2 = the
artifact could not be read at all.  The same checks run in-process from
``tests/test_ledger.py`` (the ``obs`` marker tier-1 run), so a schema
drift fails CI before it ships a consumer-breaking artifact.
"""

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

#: phase letters this repo's tracer emits (a subset of the trace_event
#: spec): X complete, i instant, C counter, M metadata
KNOWN_PHASES = {"X", "i", "C", "M"}

LEDGER_SCHEMA = "mythril-tpu-lane-ledger/2"
#: every schema this linter reads; v1 artifacts predate the autopilot's
#: per-record features/routed_by annotations but are otherwise identical
LEDGER_SCHEMAS = ("mythril-tpu-lane-ledger/1", LEDGER_SCHEMA)


def lint_trace(payload) -> list:
    """Findings for one ``--trace-out`` payload (already parsed)."""
    findings = []
    if not isinstance(payload, dict):
        return ["trace: top level must be a JSON object"]
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        return ["trace: 'traceEvents' missing or not a list"]
    for index, event in enumerate(events):
        where = f"trace event[{index}]"
        if not isinstance(event, dict):
            findings.append(f"{where}: not an object")
            continue
        for key in ("name", "ph", "pid", "tid"):
            if key not in event:
                findings.append(f"{where}: missing {key!r}")
        ph = event.get("ph")
        if ph not in KNOWN_PHASES:
            findings.append(f"{where}: unknown phase {ph!r}")
        if ph in ("X", "i", "C") and not isinstance(
            event.get("ts"), (int, float)
        ):
            findings.append(f"{where}: 'ts' missing or non-numeric")
        if ph == "X":
            dur = event.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                findings.append(
                    f"{where}: complete event needs dur >= 0, got "
                    f"{dur!r}"
                )
        if ph == "C" and not isinstance(event.get("args"), dict):
            findings.append(f"{where}: counter event needs args")
    other = payload.get("otherData")
    if isinstance(other, dict):
        dropped = other.get("dropped_events", 0)
        truncated = any(
            isinstance(e, dict) and e.get("name") == "trace.truncated"
            for e in events
        )
        if dropped and not truncated:
            findings.append(
                f"trace: {dropped} events dropped but no "
                "trace.truncated marker on the timeline"
            )
    return findings


def lint_ledger(payload) -> list:
    """Findings for one ``--lane-ledger-out`` payload."""
    from mythril_tpu.observability.ledger import (
        LEGAL_NEXT, TERMINAL_TIERS, VERDICTS,
    )

    findings = []
    if not isinstance(payload, dict):
        return ["ledger: top level must be a JSON object"]
    if payload.get("schema") not in LEDGER_SCHEMAS:
        findings.append(
            f"ledger: schema {payload.get('schema')!r} not one of "
            f"{LEDGER_SCHEMAS!r}"
        )
    aggregates = payload.get("aggregates")
    if not isinstance(aggregates, dict):
        return findings + ["ledger: 'aggregates' missing"]
    for key in ("lanes_total", "decided", "by_kind", "transitions",
                "records_kept", "records_dropped"):
        if key not in aggregates:
            findings.append(f"ledger: aggregates missing {key!r}")
    # lane conservation: every opened lane terminated in exactly one
    # tier — the invariant the whole attribution story rests on
    lanes_total = aggregates.get("lanes_total", 0)
    decided = aggregates.get("decided", {})
    decided_total = sum(decided.values()) if isinstance(
        decided, dict
    ) else -1
    if decided_total != lanes_total:
        findings.append(
            f"ledger: lane conservation violated — lanes_total="
            f"{lanes_total} but decided sums to {decided_total}"
        )
    for tier in decided if isinstance(decided, dict) else ():
        if tier not in TERMINAL_TIERS:
            findings.append(f"ledger: unknown terminal tier {tier!r}")
    conservation = payload.get("conservation")
    if isinstance(conservation, dict) and (
        conservation.get("lanes_total")
        != conservation.get("decided_total")
    ):
        findings.append(
            "ledger: conservation block disagrees with itself "
            f"({conservation})"
        )
    records = payload.get("records", [])
    if not isinstance(records, list):
        return findings + ["ledger: 'records' is not a list"]
    cap = payload.get("cap")
    if isinstance(cap, int) and len(records) > cap:
        findings.append(
            f"ledger: {len(records)} records exceed declared cap {cap}"
        )
    for record in records:
        where = f"ledger record {record.get('id', '?')}"
        path = record.get("path")
        if not isinstance(path, list) or not path or (
            path[0] != "opened"
        ):
            findings.append(f"{where}: path must start at 'opened'")
            continue
        for prev, nxt in zip(path, path[1:]):
            if prev in TERMINAL_TIERS:
                findings.append(
                    f"{where}: transition out of terminal tier "
                    f"{prev!r}"
                )
                break
            if nxt not in LEGAL_NEXT.get(prev, ()):
                findings.append(
                    f"{where}: illegal transition {prev!r} -> {nxt!r}"
                )
                break
        if path[-1] != record.get("tier"):
            findings.append(
                f"{where}: path ends at {path[-1]!r} but tier is "
                f"{record.get('tier')!r}"
            )
        if record.get("tier") not in TERMINAL_TIERS:
            findings.append(
                f"{where}: non-terminal tier {record.get('tier')!r}"
            )
        if record.get("verdict") not in VERDICTS:
            findings.append(
                f"{where}: unknown verdict {record.get('verdict')!r}"
            )
        # v2 annotations are optional per record but must be shaped
        # right when present (replay depends on them)
        features = record.get("features")
        if features is not None and not isinstance(features, dict):
            findings.append(f"{where}: 'features' is not an object")
        routed_by = record.get("routed_by")
        if routed_by is not None and not isinstance(routed_by, str):
            findings.append(f"{where}: 'routed_by' is not a string")
    return findings


def _lint_file(path: str, lint) -> int:
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (OSError, ValueError) as exc:
        print(f"{path}: unreadable ({exc})")
        return 2
    findings = lint(payload)
    for finding in findings:
        print(f"{path}: {finding}")
    if not findings:
        print(f"{path}: ok")
    return 1 if findings else 0


def _selftest() -> int:
    """Generate a trace and a ledger in-process and lint both — the
    tox wiring that keeps the emitters and this linter in lockstep."""
    import tempfile

    from mythril_tpu.observability import ledger as ledger_mod
    from mythril_tpu.observability import spans

    spans.reset_for_tests()
    ledger_mod.reset_for_tests()
    tracer = spans.get_tracer()
    tracer.enable()
    spans.set_trace_id(spans.new_trace_id())
    with spans.span("selftest.outer"):
        spans.instant("selftest.tick")
        spans.counter("selftest.gauge", value=3)
    led = ledger_mod.get_ledger()
    batch = led.begin_batch("batch_check", 4)
    batch.set_features(0, {"v": 1, "constraints": 2, "nodes": 8})
    batch.decide(0, "word", "unsat")
    batch.transition(1, "dispatched")
    batch.decide(1, "sweep", "sat")
    batch.set_features(2, {"v": 1, "constraints": 1, "nodes": 3})
    batch.set_routed(2, "tail-direct")
    batch.transition(2, "deferred")
    batch.close()  # lanes 2 and 3 settle as tail-demoted
    led.single("prune", "structural", "unsat")
    rc = 0
    with tempfile.TemporaryDirectory() as tmp:
        trace_path = os.path.join(tmp, "trace.json")
        ledger_path = os.path.join(tmp, "ledger.json")
        tracer.export_chrome(trace_path)
        led.export_json(ledger_path)
        rc |= _lint_file(trace_path, lint_trace)
        rc |= _lint_file(ledger_path, lint_ledger)
    spans.reset_for_tests()
    ledger_mod.reset_for_tests()
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0]
    )
    ap.add_argument("--trace", action="append", default=[],
                    metavar="FILE", help="--trace-out artifact(s)")
    ap.add_argument("--ledger", action="append", default=[],
                    metavar="FILE",
                    help="--lane-ledger-out artifact(s)")
    ap.add_argument("--selftest", action="store_true",
                    help="generate both artifacts in-process and lint "
                    "them (CI wiring)")
    opts = ap.parse_args()
    if opts.selftest:
        return _selftest()
    if not opts.trace and not opts.ledger:
        ap.error("nothing to lint: pass --trace/--ledger/--selftest")
    rc = 0
    for path in opts.trace:
        rc |= _lint_file(path, lint_trace)
    for path in opts.ledger:
        rc |= _lint_file(path, lint_ledger)
    return rc


if __name__ == "__main__":
    sys.exit(main())
