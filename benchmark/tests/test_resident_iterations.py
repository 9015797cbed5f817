"""The reader of ``resident_iterations_per_analysis``: the mean of the
records' count over the window, and nothing where there is no window
or the program's records do not count it."""

import pytest

from yardstick import cells


class Run:
    def __init__(self, walls):
        self.rows = [{"wall_s": wall} for wall in walls]


def _record(wall, **fields):
    return dict({"wall_s": wall, "self_s": {}}, **fields)


@pytest.fixture
def records(monkeypatch):
    from mythril_tpu import observability

    held = []
    monkeypatch.setattr(observability, "analysis_profiles",
                        lambda: list(held))
    return held


@pytest.fixture
def read():
    return cells.load("metrics", "resident_iterations_per_analysis").read


def test_mean_of_the_window_without_the_warmup(records, read):
    records += [_record(30.0, resident_iterations=4000),
                _record(12.0, resident_iterations=1537),
                _record(12.2, resident_iterations=1541),
                _record(11.9, resident_iterations=0)]
    assert read(Run([12.0, 12.2, 11.9])) == pytest.approx(1026.0)


@pytest.mark.parametrize("held", [
    [],                                          # no records at all
    [_record(1.0, resident_iterations=9)],       # fewer than the rows
    [_record(1.0, resident_iterations=9),
     _record(2.0, resident_iterations=9)],       # walls that do not pair
    [_record(1.0), _record(1.2)],                # a program without the count
])
def test_nothing_where_there_is_no_window(records, read, held):
    records += held
    assert read(Run([1.0, 1.2])) is None


def test_nothing_in_a_program_without_records(monkeypatch, read):
    from mythril_tpu import observability

    monkeypatch.delattr(observability, "analysis_profiles")
    assert read(Run([1.0])) is None
