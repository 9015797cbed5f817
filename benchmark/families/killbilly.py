"""SWC-106, a copy of ``killbilly`` of ``bench._corpus()``: ``kill()``
SELFDESTRUCTs for any caller."""

import random

from yardstick.contracts import DISPATCH, Contract, calldata
from yardstick.evm import Tx, assemble


def build(rng: random.Random) -> Contract:
    """The contract, with constants from ``rng``."""
    selector = rng.getrandbits(32)
    code = assemble(f"""
        {DISPATCH}
        DUP1; PUSH4 {selector}; EQ; PUSH @kill; JUMPI
        PUSH1 0; PUSH1 0; REVERT
      kill:
        JUMPDEST; CALLER; SUICIDE
    """)
    return Contract("killbilly", code, (
        (Tx(calldata(selector)),), (Tx(calldata(selector ^ 1)),)))
