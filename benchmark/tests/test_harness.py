"""The window loop, driven with a stub in place of ``cli.main`` and a
clock of the test's own, and the arithmetic of the end-to-end
metrics."""

import io
import json
import os

import pytest

from yardstick import cells, harness, stats
from yardstick.reference import expected_findings
from yardstick.traffic import Traffic

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _stub(cell, seed, clock, walls, fault=None):
    """An ``analyze`` that takes ``walls[i]`` seconds of the test's clock
    and reports what the reference expects, unless ``fault`` alters
    the row."""
    traffic = Traffic(cell.traffic, seed)
    contracts = {c.hex: c for index in range(-1, 40)
                 for c in traffic.round(index)}
    calls = []

    def analyze(code):
        wall = walls[len(calls) % len(walls)]
        calls.append(code)
        clock.now += wall
        row = {"wall_s": wall,
               "findings": sorted(expected_findings(
                   contracts[code], cell.config["tx_count"])),
               "errors": [],
               "counters": {"device_s": 0.5, "lanes": 4, "unsat": 1,
                            "sat_verified": 1, "fused": False, "fused_skips": 0,
                            "unhealthy_skips": 0, "cpu_auto_skips": 0,
                            "watchdog_trips": 0, "demotions": 0},
               "solver_s": {"probe_s": 0.1, "blast_s": 0.1, "cone_s": 0.0,
                            "native_s": 0.2},
               "queries": 10}
        return fault(row) if fault else row

    return analyze, calls


def _measure(name, walls, seconds, trace=False, fault=None, tracer=None):
    cell = cells.resolve(name)
    clock = Clock()
    analyze, calls = _stub(cell, 2**31 + 5, clock, walls, fault)
    result, run = harness.measure(
        cell, 2**31 + 5, seconds, trace, reset=lambda: None, analyze=analyze,
        device=lambda: {"platform": "tpu", "kind": "stub", "count": 1,
                        "memory_peak_bytes": 1},
        tracer=tracer, started=clock.now - 7.0, clock=clock,
        log=io.StringIO())
    return result, run, calls


def test_window_ends_mid_analysis_and_counts_it():
    # tree16 has one contract a round; the warm-up round comes first
    result, run, calls = _measure("tpu_forced.tree16", [3.0, 5.0, 4.0], 10)
    # warm-up 3 s; the window's analyses take 5, 4, 3: the third starts
    # at 9 s, inside the window, and ends at 12 s
    assert [row["wall_s"] for row in run.rows] == [5.0, 4.0, 3.0]
    assert len(calls) == 4
    metrics = result["metrics"]
    assert metrics["analyses_per_min"]["value"] == pytest.approx(60 * 3 / 12)
    assert metrics["analysis_p90_s"]["value"] == 5.0
    assert metrics["setup_s"]["value"] == pytest.approx(7.0 + 3.0)
    assert result["correct"] and result["attempted"] == 3
    assert result["failed"] == 0


def test_result_line_has_exactly_the_contract_keys():
    result, _, _ = _measure("myth_default.tree16", [2.0], 5)
    out, log = io.StringIO(), io.StringIO()
    harness.emit(result, out=out, log=log)
    line = json.loads(out.getvalue().splitlines()[-1])
    assert list(line) == RESULT_KEYS
    assert set(line["metrics"]) == {"analyses_per_min", "analysis_p90_s",
                                    "setup_s"}
    assert log.getvalue().splitlines()[-1].startswith("compared ")


class StubTracer:
    def __init__(self):
        self.events = []

    def annotate(self, name):
        import contextlib

        self.events.append(name)
        return contextlib.nullcontext()

    def start(self):
        self.events.append("start")

    def stop(self):
        self.events.append("stop")
        return {"window_s": 6.0, "busy_s": 3.0, "idle_share": 0.5,
                "module_s": {"jit_rounds": 2.0}, "chips": 1,
                "top_ops": [["while", 2.0]],
                "idle_gaps": [["bench.analysis.pivot_dispatch", 1.0]]}


def test_traced_run_reports_the_cells_per_layer_metrics():
    tracer = StubTracer()
    result, run, _ = _measure("tpu_forced.tree16", [3.0], 5, trace=True,
                              tracer=tracer)
    assert tracer.events.count("start") == 1
    assert set(result["metrics"]) == {
        m["name"] for m in cells.resolve("tpu_forced.tree16").per_layer}
    assert result["metrics"]["resident_kernel_s_per_analysis"]["value"] == 2.0
    assert result["metrics"]["device_decided_share"]["value"] == 0.5
    assert result["metrics"]["vm_s_per_analysis"]["value"] == \
        pytest.approx(3.0 - 0.4 - 0.5)
    assert result["device"]["busy_s"] == 3.0
    assert result["breakdown"]["idle_gaps"][0][1] == 1.0
    assert list(result)[-1] == "compared"


def test_a_run_off_the_device_fails_in_the_forced_cell():
    def fuse(row):
        row["counters"].update(fused=True, fused_skips=3)
        return row

    result, _, _ = _measure("tpu_forced.tree16", [3.0], 5, fault=fuse)
    assert not result["correct"]
    assert result["compared"]["off_device_analyses"]["value"] == 2
    # the default cell does not hold the analyses to the device
    result, _, _ = _measure("myth_default.tree16", [3.0], 5, fault=fuse)
    assert result["correct"]


def test_a_fuse_that_skips_no_frontier_keeps_the_analysis_on_the_device():
    def tripped_after_the_last_dispatch(row):
        row["counters"]["fused"] = True
        return row

    result, _, _ = _measure("tpu_forced.tree16", [3.0], 5,
                            fault=tripped_after_the_last_dispatch)
    assert result["correct"]


def test_an_altered_answer_fails():
    def shift(row):
        row["findings"] = [(swc, address + 1) for swc, address in row["findings"]]
        return row

    result, _, _ = _measure("myth_default.tree16", [3.0], 5, fault=shift)
    assert not result["correct"]
    # 24 findings an analysis, each moved: a miss and an extra each
    assert result["compared"]["finding_mismatches"]["value"] == 2 * 2 * 24


def test_nearest_rank_and_rate():
    walls = [4.0, 1.0, 3.0, 2.0, 5.0, 10.0, 6.0, 7.0, 8.0, 9.0]
    assert stats.nearest_rank(walls, 0.9) == 9.0
    assert stats.nearest_rank(walls + [11.0], 0.9) == 10.0
    assert stats.nearest_rank([7.0], 0.9) == 7.0
    assert stats.per_minute(3, 12.0) == 15.0
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


def test_every_cell_finds_its_files():
    spec = json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))
    for entry in spec["workloads"]:
        cell = cells.resolve(entry["name"])
        assert cell.chips == entry["chips"]
        assert set(cell.config["limits"]) >= {"finding_mismatches",
                                              "broken_analyses"}
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for metric in cell.per_layer:
            assert callable(cell.reader(metric["name"]))
