"""SWC-101, a copy of ``veritest_gauntlet`` of ``bench._corpus()``: four
branch diamonds on calldata bits feed an accumulator; ``acc + word``
and ``+ storage`` can overflow."""

import random

from yardstick.contracts import WORD, Contract
from yardstick.evm import Tx, assemble


def build(rng: random.Random) -> Contract:
    """The contract, with constants from ``rng``."""
    diamonds = []
    for i in range(4):
        left, right = rng.randrange(1, 256), rng.randrange(1, 256)
        diamonds.append(f"""
        DUP2; PUSH1 {1 << i}; AND; PUSH @t{i}; JUMPI
        PUSH1 {left}; ADD; PUSH @j{i}; JUMP
      t{i}:
        JUMPDEST; PUSH1 {right}; ADD; PUSH @j{i}; JUMP
      j{i}:
        JUMPDEST
        """)
    code = assemble("PUSH1 4; CALLDATALOAD; PUSH1 0\n" + "".join(diamonds)
                    + """
        DUP2; ADD
        PUSH1 0; SLOAD; ADD
        PUSH1 0; SSTORE
        STOP
    """)
    near_top = WORD - 4096  # low bits clear: no diamond taken, no overflow
    probes = [(Tx((bits).to_bytes(36, "big")),) for bits in range(16)]
    probes += [(Tx((WORD - 1).to_bytes(36, "big")),),
               (Tx(near_top.to_bytes(36, "big")),
                Tx(near_top.to_bytes(36, "big")))]
    return Contract("veritest_gauntlet", code, tuple(probes))
