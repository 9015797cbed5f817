"""The whole run below the chip check, on the CPU with the real
program, at a size a test run holds (the tree16 mix cut to depth 3, 8
functions): a sound run is correct, and the control and each planted
fault make ``correct`` come out false.

The benchmark's own runs never run these; on the chip the same checks
run as ``run.py --variant control`` (and ``--variant demoted`` in the
forced cell, whose fault only a device can show)."""

import io

import pytest

from yardstick import cells, harness, myth

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def cell():
    cell = cells.resolve("myth_default.tree16")
    entry = cell.traffic["mix"][0]
    cell.traffic = {"mix": [dict(entry, params=dict(entry["params"],
                                                    depth=3))]}
    myth.configure()
    return cell


def _run(cell, flags=()):
    config = cell.config
    result, run = harness.measure(
        cell, SEED, 2.0, False, reset=myth.fresh_state,
        analyze=lambda code: myth.analyze(
            code, config["tx_count"], [*config["flags"], *flags],
            config["execution_timeout_s"]),
        device=lambda: {"platform": "cpu"}, started=0.0, log=io.StringIO())
    return result


def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1


def test_control_is_not_correct(cell):
    result = _run(cell, cell.config["variants"]["control"]["flags"])
    assert not result["correct"]
    assert result["compared"]["finding_mismatches"]["value"] >= 1


def test_answer_altered_where_it_is_produced(cell, monkeypatch):
    from mythril_tpu.analysis.report import Issue

    produce = Issue.__init__

    def altered(self, *args, **kwargs):
        produce(self, *args, **kwargs)
        self.address += 1

    monkeypatch.setattr(Issue, "__init__", altered)
    assert not _run(cell)["correct"]


def test_step_that_returns_its_state_unchanged(cell, monkeypatch):
    from mythril_tpu.laser.ethereum.svm import LaserEVM

    monkeypatch.setattr(LaserEVM, "sym_exec", lambda self, *a, **k: None)
    assert not _run(cell)["correct"]
