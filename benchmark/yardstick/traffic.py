"""The one traffic generator.  It reads a mix file of
``benchmark/traffic/`` and draws the contracts from the seed.

A mix is a list of contract families with their parameters; each
family is a file ``benchmark/families/<family>.py``, found by its name.
The analyses come in rounds: each round holds one contract of each
entry of the mix, with constants drawn afresh, in an order drawn from
the seed.  So every seed gives the same set of shapes in every round,
and only the constants and the order differ.  Round -1 is the
warm-up's.
"""

import random
from typing import Iterator, List

from yardstick import cells
from yardstick.contracts import Contract


class Traffic:
    def __init__(self, spec: dict, seed: int):
        self.mix = spec["mix"]
        self.seed = seed
        self.builders = {entry["family"]: cells.load("families",
                                                     entry["family"]).build
                         for entry in self.mix}

    def round(self, index: int) -> List[Contract]:
        contracts = [
            self.builders[entry["family"]](
                random.Random(f"{self.seed}/{index}/{position}"),
                **entry.get("params", {}))
            for position, entry in enumerate(self.mix)
        ]
        random.Random(f"{self.seed}/{index}").shuffle(contracts)
        return contracts

    def rounds(self, start: int = 0) -> Iterator[List[Contract]]:
        index = start
        while True:
            yield self.round(index)
            index += 1
