"""Profiling harness for the -t3 depth rows (CDCL iteration loop).

Runs one contract at transaction depth 3 with NO execution cap and
prints the wall, the self seconds of each span in the analysis's
record (the same spans ``--trace-out`` exports — observability/spans.py
is the single timing source, so this output and a trace file can never
disagree), the solver split, native-CDCL counters, and (with
MYTHRIL_CONE_HISTO=1) the per-query cone-size histogram.

Usage:  JAX_PLATFORMS=cpu python scripts/profile_t3.py \
            [ether_send|overflow|batchtoken] [timeout_s] \
            [--trace-out FILE]

``--trace-out FILE`` additionally records the full event timeline and
writes Chrome/Perfetto trace_event JSON (open at
https://ui.perfetto.dev).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def main() -> None:
    import logging

    logging.basicConfig(level=logging.CRITICAL)
    logging.disable(logging.CRITICAL)

    argv = list(sys.argv[1:])
    trace_out = None
    if "--trace-out" in argv:
        flag = argv.index("--trace-out")
        if flag + 1 >= len(argv):
            sys.exit("--trace-out needs a file path")
        trace_out = argv[flag + 1]
        del argv[flag:flag + 2]

    which = argv[0] if argv else "batchtoken"
    timeout = int(argv[1]) if len(argv) > 1 else 3600

    if which == "batchtoken":
        code = bench.batchtoken_contract()
        expected = {"101"}
    else:
        path = os.path.join(bench.REFERENCE_INPUTS, f"{which}.sol.o")
        code = open(path).read().strip()
        expected = {"101", "105"} if which == "ether_send" else {"101"}

    from mythril_tpu.observability import spans as obs_spans
    from mythril_tpu.support.support_args import args

    for key, value in bench.MODES["full"].items():
        setattr(args, key, value)

    # the event timeline only when a trace file was requested (honors
    # MYTHRIL_TPU_TRACE=0); the analysis record is always kept
    tracer = obs_spans.get_tracer()
    if trace_out is not None:
        tracer.enable()

    bench.DEVICE_STATUS = "cpu-only"
    t0 = time.time()
    found, row = bench._analyze_one(
        f"{which}_t3", code, 3, execution_timeout=timeout, max_depth=128
    )
    row["total_wall_s"] = round(time.time() - t0, 2)
    row["expected_ok"] = bool(expected & found)
    # self seconds by span name (top 12), from the row's analysis
    # record; fleet workers' lease walls ride the tracer's totals
    totals = dict(row["span_self_s"])
    totals.update({name: seconds
                   for name, seconds in tracer.totals_snapshot().items()
                   if name.startswith("fleet.worker:")})
    row["span_totals_s"] = {
        name: round(seconds, 3)
        for name, seconds in sorted(
            totals.items(), key=lambda kv: -kv[1]
        )[:12]
    }
    # word-tier share: how much of this run's wall went to the
    # abstract-propagation pass, and how many queries it retired
    # before the blaster (row already carries word_decided_unsat/
    # word_decided_sat/word_tightened_bits via DispatchStats)
    word_s = sum(
        seconds for name, seconds in totals.items()
        if name.startswith("word.")
    )
    row["word_span_s"] = round(word_s, 3)
    row["word_span_share"] = round(
        word_s / row["total_wall_s"], 4
    ) if row["total_wall_s"] else 0.0
    row["word_decided_lanes"] = (
        row.get("word_decided_unsat", 0) + row.get("word_decided_sat", 0)
    )
    # frontier-tier share: wall spent in event-driven frontier rounds
    # (adjacency-gather BCP + in-kernel first-UIP learning) and the
    # learned clauses harvested — the row already carries
    # frontier_steps / learned_clauses via DispatchStats
    frontier_s = sum(
        seconds for name, seconds in totals.items()
        if name.startswith("frontier.")
    )
    row["span_frontier_s"] = round(frontier_s, 3)
    row["frontier_span_share"] = round(
        frontier_s / row["total_wall_s"], 4
    ) if row["total_wall_s"] else 0.0
    row["frontier_learned_clauses"] = row.get("learned_clauses", 0)
    # resident-solver exit taxonomy: how each persistent dispatch
    # ended (all lanes retired / iteration budget / device-side stall
    # watchdog) plus the wall spent inside resident.solve spans — the
    # row already carries the counters via DispatchStats, this block
    # makes the split legible next to the other tier shares
    resident_s = sum(
        seconds for name, seconds in totals.items()
        if name.startswith("resident.")
    )
    row["span_resident_s"] = round(resident_s, 3)
    row["resident_span_share"] = round(
        resident_s / row["total_wall_s"], 4
    ) if row["total_wall_s"] else 0.0
    row["resident_exits"] = {
        "all_decided": row.get("resident_exit_all_decided", 0),
        "budget": row.get("resident_exit_budget", 0),
        "watchdog": row.get("resident_exit_watchdog", 0),
    }
    # lockstep-tier share: wall spent executing batched straight-line
    # segments over sibling states (svm.segment spans) — the row
    # already carries states_stepped / segment_s / plane_*_bits via
    # DispatchStats, so steps-per-second here is cross-checkable
    # against the bench headline's states_per_s
    lockstep_s = sum(
        seconds for name, seconds in totals.items()
        if name.startswith("svm.segment")
    )
    row["span_lockstep_s"] = round(lockstep_s, 3)
    row["lockstep_span_share"] = round(
        lockstep_s / row["total_wall_s"], 4
    ) if row["total_wall_s"] else 0.0
    # veritesting-tier share: wall spent in the re-convergence merge
    # and frontier-subsumption passes (svm.merge / svm.subsume spans)
    # — the row already carries merges / merge_ites / merge_aborts /
    # subsumed_lanes via DispatchStats, so the cost of the tier is
    # legible next to the states it saved
    merge_s = sum(
        seconds for name, seconds in totals.items()
        if name.startswith(("svm.merge", "svm.subsume"))
    )
    row["span_merge_s"] = round(merge_s, 3)
    row["merge_span_share"] = round(
        merge_s / row["total_wall_s"], 4
    ) if row["total_wall_s"] else 0.0
    row["subsumed_lanes"] = row.get("subsumed_lanes", 0)
    # NEEDS_HOST boundary breakdown: which opcode (or "cap" /
    # "end-of-code") parked lanes back to serial stepping, sorted by
    # count — the per-cause view behind the bench headline's
    # host_boundaries_per_1k_states, and the worklist for the next
    # opcode worth teaching the memory/storage/keccak planes
    causes = row.get("boundary_causes") or {}
    row["boundary_cause_split"] = dict(
        sorted(causes.items(), key=lambda kv: (-kv[1], kv[0]))
    )
    steps = row.get("states_stepped", 0)
    row["host_boundaries_per_1k_states"] = round(
        row.get("needs_host_boundaries", 0) / steps * 1000, 2
    ) if steps else None
    # fleet-worker shares (populated when the run shards via
    # MYTHRIL_TPU_FLEET_WORKERS / --workers: each lease's wall lands
    # under fleet.worker:<id> via Tracer.add_external_total, so the
    # per-worker split of a sharded profile is attributable here)
    worker_spans = {
        name.split(":", 1)[1]: round(seconds, 3)
        for name, seconds in totals.items()
        if name.startswith("fleet.worker:")
    }
    if worker_spans:
        total_worker_s = sum(worker_spans.values())
        row["fleet_worker_span_s"] = worker_spans
        row["fleet_worker_span_share"] = {
            worker: round(seconds / total_worker_s, 4)
            for worker, seconds in worker_spans.items()
        } if total_worker_s else {}

    from mythril_tpu.smt.solver import get_blast_context

    ctx = get_blast_context()
    solver = ctx.solver
    row["cdcl_conflicts"] = solver.conflicts
    row["pool_clauses"] = ctx.clause_count
    try:
        row["cdcl_propagations"] = solver.propagations
        row["cdcl_decisions"] = solver.decisions
        row["cdcl_restarts"] = solver.restarts
        row["cdcl_reduces"] = solver.reduces
        row["cdcl_vivified"] = solver.vivified_lits
    except AttributeError:
        pass
    histo = getattr(ctx, "cone_histogram", None)
    if histo:
        row["cone_histogram"] = histo
    if trace_out:
        row["trace_out"] = tracer.export_chrome(trace_out)
    print(json.dumps(row, indent=1))


if __name__ == "__main__":
    main()
