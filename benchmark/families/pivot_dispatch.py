"""External functions behind the dispatcher solc's legacy code
generator emits (``ContractCompiler::appendInternalSelector``): the
selectors sorted, split at the middle one by ``DUP1 PUSH4 pivot GT
JUMPI`` while more than four remain, then a chain of ``EQ`` jumps; a
call shorter than four bytes goes to the fallback, which reverts.  The
selectors come from the mix file; each body is
:func:`yardstick.contracts.asserted_function`, and function ``i % 4 ==
1`` is dead.
"""

import random
from typing import List

from yardstick.contracts import (DISPATCH, REVERT, Contract,
                                 asserted_function, calldata)
from yardstick.evm import Tx, assemble


def _dispatch(ids: List[int], lines: List[str]) -> None:
    if len(ids) > 4:
        pivot = ids[len(ids) // 2]
        lines.append(f"DUP1; PUSH4 {pivot}; GT; PUSH @less{pivot}; JUMPI")
        _dispatch(ids[len(ids) // 2:], lines)
        lines += [f"less{pivot}:", "JUMPDEST"]
        _dispatch(ids[:len(ids) // 2], lines)
        return
    for selector in ids:
        lines.append(f"DUP1; PUSH4 {selector}; EQ; PUSH @f{selector}; JUMPI")
    lines.append("PUSH @fallback; JUMP")


def build(rng: random.Random, selectors: List[str], guard_bits: int = 16,
          guard: str = "add") -> Contract:
    """The contract, with guard constants from ``rng``."""
    ids = sorted(int(s, 16) for s in selectors)
    lines = ["PUSH1 4; CALLDATASIZE; LT; PUSH @fallback; JUMPI", DISPATCH]
    _dispatch(ids, lines)
    lines += ["fallback:", "JUMPDEST", REVERT]
    probes = [(Tx(b"\x01\x02"),), (Tx(calldata(0)),)]
    for i, selector in enumerate(ids):
        body, paths = asserted_function(rng, i, selector, guard_bits, guard,
                                        i % 4 == 1)
        lines += [f"f{selector}:", "JUMPDEST", *body]
        probes += paths
    return Contract("pivot_dispatch", assemble("\n".join(lines)),
                    tuple(probes))
