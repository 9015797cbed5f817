"""``bench.scale_contract``'s dispatcher: 2**depth external functions
behind a binary split that tests one selector bit per level
(``DUP1 PUSH bit AND JUMPI``).  solc does not emit this: it compares
the selector with pivots (:mod:`pivot_dispatch`), whose 256-bit
comparisons put every guard's cone past the device's gather caps, so a
forced analysis of that shape makes no dispatch.  Each body is
:func:`yardstick.contracts.asserted_function`; function ``i % 4 == 1``
is dead.
"""

import random

from yardstick.contracts import DISPATCH, Contract, asserted_function
from yardstick.evm import assemble


def build(rng: random.Random, depth: int, guard_bits: int = 16,
          guard: str = "add") -> Contract:
    """The contract, with guard constants from ``rng``."""
    lines = [DISPATCH, "PUSH @nE; JUMP"]
    probes = []

    def label(prefix):
        return "n" + (prefix or "E")

    prefixes = [""]
    for level in range(depth):
        grown = []
        for prefix in prefixes:
            lines += [f"{label(prefix)}:", "JUMPDEST",
                      f"DUP1; PUSH {1 << level}; AND; "
                      f"PUSH @{label(prefix + '1')}; JUMPI",
                      f"PUSH @{label(prefix + '0')}; JUMP"]
            grown += [prefix + "0", prefix + "1"]
        prefixes = grown
    for i, prefix in enumerate(prefixes):
        body, paths = asserted_function(rng, i, int(prefix[::-1], 2),
                                        guard_bits, guard, i % 4 == 1)
        lines += [f"{label(prefix)}:", "JUMPDEST", *body]
        probes += paths
    return Contract("selector_tree", assemble("\n".join(lines)),
                    tuple(probes))
