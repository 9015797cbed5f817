"""The plain reference that decides ``correct``.

For each analysed contract the reference runs the family's probes on
the concrete interpreter of :mod:`yardstick.evm` (probes longer than the
configuration's transaction count are left out) and takes the union of
the events: the (SWC id, bytecode address) pairs that Mythril's
detection semantics report for this contract at that depth.  The
probes take every path of the contract, so the union is the whole
expected set.  An analysis is compared by the symmetric difference of
that set and the findings the program reported: a miss and an extra
finding each count one.
"""

from typing import FrozenSet, Iterable, Tuple

from yardstick.contracts import Contract
from yardstick.evm import run_sequence

Finding = Tuple[str, int]


def expected_findings(contract: Contract, tx_count: int) -> FrozenSet[Finding]:
    events = set()
    for probe in contract.probes:
        if len(probe) <= tx_count:
            events |= run_sequence(contract.code, probe)[0]
    return frozenset(events)


def mismatches(expected: Iterable[Finding], reported: Iterable[Finding]) -> int:
    return len(set(expected) ^ set(reported))
