"""The trace reduction: exact values on hand-made events, and known
values on a small trace recorded on a v5e (``record_trace.py``)."""

import os

import pytest

from yardstick import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "trace_fixture.xplane.pb")
MS = 1_000_000


def test_busy_idle_and_gap_owners_on_hand_made_events():
    events = {
        "devices": [{
            "plane": "/device:TPU:0",
            # two overlapping ops, then one clipped by the window's end
            "ops": [(10 * MS, 30 * MS, "fusion"), (20 * MS, 40 * MS, "while"),
                    (90 * MS, 120 * MS, "while")],
            "modules": [(10 * MS, 40 * MS, "jit_rounds(7)"),
                        (90 * MS, 120 * MS, "jit_rounds(7)")],
        }],
        "host": [(0, 100 * MS, "bench.traced"),
                 (0, 60 * MS, "bench.analysis.selector_tree"),
                 (60 * MS, 100 * MS, "bench.reset")],
    }
    reduced = trace.reduce(events)
    assert reduced["window_s"] == pytest.approx(0.1)
    assert reduced["busy_s"] == pytest.approx(0.040)
    assert reduced["idle_share"] == pytest.approx(0.6)
    assert reduced["module_s"] == {"jit_rounds": pytest.approx(0.060)}
    # each gap is named by the annotation open at its midpoint
    assert reduced["idle_gaps"] == [
        ["bench.reset", pytest.approx(0.050)],
        ["bench.analysis.selector_tree", pytest.approx(0.010)],
    ]
    assert dict(reduced["top_ops"]) == {"while": pytest.approx(0.050),
                                       "fusion": pytest.approx(0.020)}


def test_chips_are_averaged():
    one = {"plane": "/device:TPU:0", "ops": [(0, 50 * MS, "a")], "modules": []}
    two = {"plane": "/device:TPU:1", "ops": [(0, 100 * MS, "a")], "modules": []}
    reduced = trace.reduce({"devices": [one, two],
                            "host": [(0, 100 * MS, "bench.traced")]})
    assert reduced["busy_s"] == pytest.approx(0.075)
    assert reduced["chips"] == 2


def test_recorded_trace():
    """Two ``rounds`` calls (20 tanh(x @ x) steps at 512x512) with a
    50 ms sleep between, then a 20 ms reset, on one v5e (my chip run,
    PR 22).  The values were read once and are pinned here."""
    reduced = trace.reduce(trace.load(FIXTURE))
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.073288234)
    assert reduced["busy_s"] == pytest.approx(3.7757e-05)
    assert reduced["module_s"]["jit_rounds"] == pytest.approx(3.7767e-05)
    assert reduced["idle_gaps"][:2] == [
        ["bench.analysis.fixture", pytest.approx(0.050541248)],
        ["bench.reset", pytest.approx(0.022709226)],
    ]
    assert reduced["top_ops"][0][0] == "%while"
