"""What a contract family returns, and the pieces families share.

A family is a file ``benchmark/families/<name>.py`` whose ``build``
takes a ``random.Random`` and the parameters of its mix entry and
returns a :class:`Contract`: its runtime code, and probes — transaction
sequences that together take every path of the contract — for the
reference to run.  The seed draws the constants.  Every drawn constant
has a fixed push width, so a family's code has the same length, layout
and findings' addresses for every seed: the seed never changes a
contract's shape or its SWC classes.  Families import nothing of the
program.
"""

import random
from dataclasses import dataclass
from typing import Tuple

from yardstick.evm import Tx

WORD = 1 << 256

# the selector: the first four bytes of the calldata
DISPATCH = "PUSH1 0; CALLDATALOAD; PUSH1 0xe0; SHR"
REVERT = "PUSH1 0; DUP1; REVERT"


@dataclass(frozen=True)
class Contract:
    family: str
    code: bytes
    probes: Tuple[Tuple[Tx, ...], ...]

    @property
    def hex(self) -> str:
        return self.code.hex()


def calldata(selector: int, *words: int) -> bytes:
    return selector.to_bytes(4, "big") + b"".join(
        word.to_bytes(32, "big") for word in words)


def asserted_function(rng: random.Random, i: int, selector: int,
                      guard_bits: int, guard: str, dead: bool):
    """The body of external function ``i`` (after its JUMPDEST, with
    the selector on the stack) and the probes that take its paths.

    The body is one ``assert`` on a masked calldata word, as solc
    before 0.8 compiles it (``cond PUSH ok JUMPI INVALID``), with the
    guard ``((x & mask) + addend) & mask == target`` (or ``* odd``),
    which the host's probe does not guess: its true side is a device
    lane.  Both sides end in a finding of their own, so a wrong verdict
    on either changes the findings: the false side in SWC-110 at its
    INVALID, the true side in SWC-106 (``selfdestruct(msg.sender)``).
    No JUMPI follows the guard, so the guards are the last frontier of
    a transaction.  A ``dead`` function asks the selector's low bits for
    a value the dispatcher has excluded instead, and the SELFDESTRUCT
    behind it must never be reported."""
    ok = f"ok{i}"
    if dead:
        wrong = ((selector & 3) + 1) & 3
        return ([f"DUP1; PUSH1 3; AND; PUSH1 {wrong}; EQ; PUSH @{ok}; JUMPI",
                 REVERT, f"{ok}:", "JUMPDEST; CALLER; SELFDESTRUCT"],
                [(Tx(calldata(selector, 0)),)])
    width = (guard_bits + 7) // 8
    mask = (1 << guard_bits) - 1
    target = rng.getrandbits(guard_bits)
    if guard == "mul":
        factor = rng.getrandbits(guard_bits) | 1
        op = f"PUSH{width} {factor}; MUL"
        passing = target * pow(factor, -1, mask + 1) & mask
    else:
        addend = rng.getrandbits(guard_bits)
        op = f"PUSH{width} {addend}; ADD"
        passing = (target - addend) & mask
    return ([f"PUSH1 4; CALLDATALOAD; PUSH{width} {mask}; AND; {op}; "
             f"PUSH{width} {mask}; AND; PUSH{width} {target}; EQ; "
             f"PUSH @{ok}; JUMPI", "INVALID",
             f"{ok}:", "JUMPDEST; CALLER; SELFDESTRUCT"],
            [(Tx(calldata(selector, passing)),),
             (Tx(calldata(selector, (passing + 1) & mask)),)])
