"""Unit tests for the straggler-aware sweep scheduler: lane buckets,
round-ladder budgets, hot-tier row selection, and the cross-dispatch
lane coalescer's admission window.

Marked ``perf``: these pin the scheduling policy the perf numbers in
docs/perf.md depend on, so a bench regression hunt can run exactly this
subset (``pytest -m perf``).  They stay tier-1 (fast, CPU-only, no
device work).
"""

import numpy as np
import pytest

from mythril_tpu.ops import coalesce as CO
from mythril_tpu.ops.batched_sat import (
    GATHER_ROUND_BUDGETS,
    dispatch_stats,
    lane_bucket,
)
from mythril_tpu.ops.coalesce import LaneCoalescer
from mythril_tpu.ops.pallas_prop import (
    ROUND_BUDGETS,
    _hot_first_perm,
    _hot_row_mask,
    _ladder_budgets,
)

pytestmark = pytest.mark.perf


class _Ctx:
    def __init__(self, generation=1):
        self.generation = generation


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Fresh stats + coalescer per test; pin the env knobs so ambient
    MYTHRIL_TPU_* settings can't skew the admission decisions."""
    for var in ("MYTHRIL_TPU_COALESCE", "MYTHRIL_TPU_COALESCE_WINDOW",
                "MYTHRIL_TPU_COALESCE_FILL", "MYTHRIL_TPU_ROUND_LADDER"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("MYTHRIL_TPU_COALESCE", "1")
    dispatch_stats.reset()
    yield
    dispatch_stats.reset()


# ------------------------------------------------------------- buckets


def test_lane_bucket_powers_of_two():
    assert lane_bucket(1) == 4
    assert lane_bucket(4) == 4
    assert lane_bucket(5) == 8
    assert lane_bucket(9, floor=8) == 16
    assert lane_bucket(158) == 256


def test_ladder_budgets_cover_total():
    """The geometric set must cover any step budget (last entry
    repeats), and the ladder collapses to one round when disabled."""
    budgets = _ladder_budgets(2048, interpret=False)
    assert sum(budgets) >= 2048
    assert tuple(budgets[: len(ROUND_BUDGETS)]) == ROUND_BUDGETS
    assert set(budgets[len(ROUND_BUDGETS):]) <= {ROUND_BUDGETS[-1]}
    assert sum(GATHER_ROUND_BUDGETS) <= 2048  # gather grid stays small


def test_ladder_env_kill_switch(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_ROUND_LADDER", "0")
    assert _ladder_budgets(768, interpret=False) == [768]


# ------------------------------------------------------------ hot tier


def test_hot_row_mask_narrow_and_touched():
    """Hot = narrow clauses (unit fuel) plus rows touching a seed
    column; wide untouched rows stay cold."""
    urow = np.asarray([0, 0, 1, 1, 1, 1, 2, 2, 2, 2], dtype=np.int64)
    ulit = np.asarray([2, -3, 4, 5, 6, 7, 8, 9, 10, 11], dtype=np.int32)
    width = np.asarray([2, 4, 4], dtype=np.float32)
    mask = _hot_row_mask(urow, ulit, width, np.asarray([9]))
    assert mask.tolist() == [True, False, True]  # narrow / cold / touched


def test_hot_row_mask_ignores_zero_width_rows():
    """Tautology-dropped rows (width 0) must never be hot — they have
    no coordinates to sweep."""
    mask = _hot_row_mask(
        np.empty(0, np.int64), np.empty(0, np.int32),
        np.asarray([0.0, 2.0], np.float32), np.empty(0, np.int64),
    )
    assert mask.tolist() == [False, True]


def test_hot_first_perm_is_stable_partition():
    mask = np.asarray([False, True, False, True])
    order, new_pos = _hot_first_perm(mask)
    assert order.tolist() == [1, 3, 0, 2]  # hot rows first, stable
    assert new_pos[order].tolist() == [0, 1, 2, 3]
    assert mask[order].tolist() == [True, True, False, False]


# ----------------------------------------------------------- coalescer


def _sets(*vals):
    """n disjoint single-literal assumption sets."""
    return [[v] for v in vals]


def test_coalescer_first_batch_never_deferred():
    co = LaneCoalescer()
    extras = co.admit(_Ctx(), _sets(2), [None], [None])
    assert extras == []  # admitted immediately, nothing queued


def test_coalescer_defers_underfilled_then_merges(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_COALESCE_WINDOW", "1")
    co = LaneCoalescer()
    ctx = _Ctx()
    assert co.admit(ctx, _sets(2, 3, 4, 5, 6, 7), [None] * 6,
                    [None] * 6) == []
    # 2 lanes against a floor-8 bucket is badly underfilled: deferred
    assert co.admit(ctx, _sets(8, 9), [None] * 2, [None] * 2) is None
    assert dispatch_stats.coalesce_deferred == 2
    # next batch merges the queue; lanes already in the batch are
    # dropped from the extras (their merged twin answers for them)
    extras = co.admit(ctx, _sets(9, 10), [None] * 2, [None] * 2)
    assert extras is not None
    assert sorted(q.lits for q in extras) == [[8]]
    assert not co.queue


def test_coalescer_window_bound(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_COALESCE_WINDOW", "1")
    co = LaneCoalescer()
    ctx = _Ctx()
    co.admit(ctx, _sets(2), [None], [None])
    assert co.admit(ctx, _sets(3), [None], [None]) is None
    # window exhausted: the next underfilled batch ships anyway,
    # carrying the queued lane
    extras = co.admit(ctx, _sets(4), [None], [None])
    assert [q.lits for q in extras] == [[3]]


def test_coalescer_force_now_bypasses_window():
    co = LaneCoalescer()
    ctx = _Ctx()
    co.admit(ctx, _sets(2), [None], [None])
    extras = co.admit(ctx, _sets(3), [None], [None], force_now=True)
    assert extras == []  # fuse-retry dispatches must reach the device


def test_coalescer_full_bucket_ships_immediately():
    co = LaneCoalescer()
    ctx = _Ctx()
    co.admit(ctx, _sets(2), [None], [None])
    sets = _sets(*range(10, 17))  # 7 of 8 slots >= 0.75 fill
    assert co.admit(ctx, sets, [None] * 7, [None] * 7) == []


def test_coalescer_generation_scoped():
    """A new blast-context generation drops the queue: stale lanes
    reference retired node ids and must never merge forward."""
    co = LaneCoalescer()
    co.admit(_Ctx(generation=1), _sets(2), [None], [None])
    assert co.admit(_Ctx(generation=1), _sets(3), [None], [None]) is None
    assert co.drain(_Ctx(generation=2)) == []


def test_coalescer_requeue_preserves_lanes():
    co = LaneCoalescer()
    ctx = _Ctx()
    co.admit(ctx, _sets(2), [None], [None])
    co.admit(ctx, _sets(3, 4), [None] * 2, [None] * 2)
    extras = co.drain(ctx)
    assert len(extras) == 2
    co.requeue(ctx, extras)  # prefetch never launched: lanes restored
    assert sorted(q.lits for q in co.drain(ctx)) == [[3], [4]]


def test_coalescer_disabled_passes_through(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_COALESCE", "0")
    co = LaneCoalescer()
    ctx = _Ctx()
    co.admit(ctx, _sets(2), [None], [None])
    assert co.admit(ctx, _sets(3), [None], [None]) == []


def test_reset_coalescer_clears_queue(monkeypatch):
    # a CLI process's reset: a serve engine started earlier in this
    # process (another test file) leaves the soft serve-mode reset on
    monkeypatch.setattr(CO, "_serve_mode", False)
    co = CO.get_coalescer()
    ctx = _Ctx()
    co.admit(ctx, _sets(2), [None], [None])
    co.admit(ctx, _sets(3), [None], [None])
    assert co.queue
    CO.reset_coalescer()
    assert not co.queue


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
