"""SWC-115, a copy of ``origin_gate`` of ``bench._corpus()``: a
SELFDESTRUCT behind ``tx.origin == constant``.  Not SWC-106: the
attacker is not that origin."""

import random

from yardstick.contracts import Contract
from yardstick.evm import ATTACKER, Tx, assemble


def build(rng: random.Random) -> Contract:
    """The contract, with constants from ``rng``."""
    origin = rng.randrange(1, 256)
    code = assemble(f"""
        ORIGIN; PUSH1 {origin}; EQ; PUSH @ok; JUMPI
        PUSH1 0; PUSH1 0; REVERT
      ok:
        JUMPDEST; CALLER; SUICIDE
    """)
    return Contract("origin_gate", code, (
        (Tx(b"", ATTACKER, ATTACKER),), (Tx(b"", origin, origin),)))
