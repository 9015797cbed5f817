"""The generators and the reference: seeds, shapes, expected findings."""

import json
import os

import pytest

from yardstick import cells
from yardstick.evm import Tx, assemble, execute, jumpdests, run_sequence
from yardstick.reference import expected_findings, mismatches
from yardstick.traffic import Traffic

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
MIXES = sorted(name[:-5] for name in os.listdir(TRAFFIC_DIR))
BIG_SEED = 2**31 + 12345


def _mix(name):
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as handle:
        return json.load(handle)


def _tree(seed=0, **params):
    import random

    entry = _mix("tree16")["mix"][0]
    return cells.load("families", entry["family"]).build(
        random.Random(seed), **dict(entry["params"], **params))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_contracts(mix):
    first = Traffic(_mix(mix), BIG_SEED)
    second = Traffic(_mix(mix), BIG_SEED)
    for index in (-1, 0, 1, 7):
        assert ([c.code for c in first.round(index)]
                == [c.code for c in second.round(index)])


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_constants_same_shapes(mix):
    one = Traffic(_mix(mix), BIG_SEED).round(0)
    other = Traffic(_mix(mix), BIG_SEED + 1).round(0)
    assert ({c.code for c in one}.isdisjoint({c.code for c in other}))
    by_family = lambda cs: sorted((c.family, len(c.code)) for c in cs)
    assert by_family(one) == by_family(other)
    # the findings sit at the same addresses for every seed
    findings = lambda cs: sorted((c.family, sorted(expected_findings(c, 2)))
                                 for c in cs)
    assert findings(one) == findings(other)


def test_rounds_hold_every_family_once_in_seeded_order():
    spec = _mix("swc_corpus")
    orders = {tuple(c.family for c in Traffic(spec, seed).round(0))
              for seed in range(12)}
    assert all(sorted(order) == sorted(e["family"] for e in spec["mix"])
               for order in orders)
    assert len(orders) > 1


# expected findings, at -t 1 and -t 2, of the bench.py originals' shapes
EXPECTED = {
    ("killbilly", 2): [("SWC-106", 24)],
    ("overflow_token", 1): [],
    ("overflow_token", 2): [("SWC-101", 29)],
    ("origin_gate", 2): [("SWC-115", 7)],
    ("veritest_gauntlet", 1): [("SWC-101", 102)],
    ("veritest_gauntlet", 2): [("SWC-101", 102), ("SWC-101", 106)],
}


@pytest.mark.parametrize("family,tx_count", sorted(EXPECTED))
def test_reference_findings(family, tx_count):
    import random

    for seed in (0, BIG_SEED):
        contract = cells.load("families", family).build(random.Random(seed))
        assert sorted(expected_findings(contract, tx_count)) == \
            EXPECTED[(family, tx_count)]


@pytest.mark.parametrize("tx_count", [1, 2])
def test_every_live_guard_side_of_tree16_is_a_finding(tx_count):
    """12 live functions: SWC-110 on each false side, SWC-106 on each
    true side; nothing behind the 4 dead ones."""
    findings = sorted(expected_findings(_tree(BIG_SEED), tx_count))
    kinds = [swc for swc, _ in findings]
    assert (kinds.count("SWC-110"), kinds.count("SWC-106")) == (12, 12)
    assert len({address for _, address in findings}) == 24


def test_erc721_dispatches_as_solc_does():
    """Sorted selectors, split at the middle by GT while more than four
    remain: 16 -> 8 + 8 -> 4 x 4 EQ chains, so 3 pivots and 16 EQs."""
    import random

    entry = _mix("erc721")["mix"][0]
    contract = cells.load("families", entry["family"]).build(
        random.Random(3), **entry["params"])
    text = contract.code.hex()
    pivots = [s for s in entry["params"]["selectors"] if f"8063{s}11" in text]
    assert len(pivots) == 3
    assert text.count("8063") == 3 + 16
    findings = expected_findings(contract, 2)
    assert len(findings) == 24 and ("SWC-110", 3) not in findings


def test_probes_reach_every_block_but_the_dead_ones():
    contract = _tree(5)
    reached = set()
    for probe in contract.probes:
        reached |= run_sequence(contract.code, probe)[1]
    # only the blocks behind the dead functions 1, 5, 9 and 13 stay unreached
    assert len(jumpdests(contract.code) - reached) == 4


def test_a_new_family_is_a_new_file():
    families = os.path.join(os.path.dirname(TRAFFIC_DIR), "families")
    used = {entry["family"] for mix in MIXES for entry in _mix(mix)["mix"]}
    assert used <= {name[:-3] for name in os.listdir(families)
                    if name.endswith(".py")}
    with pytest.raises(KeyError):
        Traffic({"mix": [{"family": "no_such_family"}]}, 1)


def test_interpreter_events():
    code = assemble("""
        ORIGIN; PUSH1 1; EQ; PUSH @a; JUMPI
      a:
        JUMPDEST
        PUSH1 4; CALLDATALOAD; PUSH1 0; SLOAD; ADD; PUSH1 0; SSTORE
        CALLER; SUICIDE
    """)
    outcome = execute(code, Tx((2**256 - 1).to_bytes(36, "big")),
                      {0: 5}, attacker_only=True)
    assert outcome.status == "selfdestruct"
    assert ("SWC-115", 7) in outcome.events
    assert ("SWC-106", len(code) - 1) in outcome.events
    assert execute(assemble("PUSH1 1; INVALID"), Tx(b""), {}, True).events \
        == {("SWC-110", 2)}
    # a SELFDESTRUCT ends the call: the overflow never reaches STOP
    assert not [e for e in outcome.events if e[0] == "SWC-101"]
    outcome = execute(code, Tx(b"", caller=1, origin=1), {}, False)
    assert not [e for e in outcome.events if e[0] == "SWC-106"]


def test_mismatches_count_misses_and_extras():
    assert mismatches({("SWC-106", 1)}, [("SWC-106", 1)]) == 0
    assert mismatches({("SWC-106", 1)}, []) == 1
    assert mismatches({("SWC-106", 1)}, [("SWC-106", 2)]) == 2
    assert mismatches(set(), [("SWC-101", 9)]) == 1
