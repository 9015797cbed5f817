"""SWC-101, a copy of ``overflow_token`` of ``bench._corpus()``:
``add(uint256)`` adds the argument to a balance slot unchecked; the
second call can overflow it."""

import random

from yardstick.contracts import DISPATCH, WORD, Contract, calldata
from yardstick.evm import Tx, assemble


def build(rng: random.Random) -> Contract:
    """The contract, with constants from ``rng``."""
    selector, slot = rng.getrandbits(32), rng.getrandbits(8)
    code = assemble(f"""
        {DISPATCH}
        DUP1; PUSH4 {selector}; EQ; PUSH @add; JUMPI
        PUSH1 0; PUSH1 0; REVERT
      add:
        JUMPDEST
        PUSH1 4; CALLDATALOAD
        PUSH1 {slot}; SLOAD
        ADD
        PUSH1 {slot}; SSTORE
        STOP
    """)
    return Contract("overflow_token", code, (
        (Tx(calldata(selector ^ 1, 1)),),
        (Tx(calldata(selector, 1)),),
        (Tx(calldata(selector, 1)), Tx(calldata(selector, WORD - 1))),
    ))
