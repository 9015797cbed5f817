"""A small EVM of the benchmark's own: an assembler, and a concrete
interpreter that the plain reference runs.

The assembler is a copy of ``mythril_tpu/support/assembler.py`` with its
own opcode table, so that no change to the program can move the
contracts the benchmark generates.  The interpreter runs one
transaction at a time on concrete inputs, over the opcodes the
benchmark's contract families use, and records the events that the
detection semantics report:

- ``SWC-106``: a SELFDESTRUCT reached in a sequence whose every
  transaction has caller == origin == the attacker;
- ``SWC-115``: a JUMPI whose condition derives from ORIGIN;
- ``SWC-110``: an INVALID reached (a failed ``assert`` before solc 0.8);
- ``SWC-101``: an ADD, SUB or MUL whose true result leaves 256 bits,
  where the result reaches a sink (SSTORE, or a JUMPI condition) in a
  transaction that ends with STOP or RETURN.  The event carries the
  address of the arithmetic instruction.
"""

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Set, Tuple

WORD = 1 << 256
MASK = WORD - 1

# Mythril's attacker actor (mythril/laser/ethereum/transaction/symbolic.py)
ATTACKER = 0xDEADBEEFDEADBEEFDEADBEEFDEADBEEFDEADBEEF

OPCODES: Dict[str, int] = {
    "STOP": 0x00, "ADD": 0x01, "MUL": 0x02, "SUB": 0x03,
    "LT": 0x10, "GT": 0x11, "EQ": 0x14, "ISZERO": 0x15, "AND": 0x16,
    "SHR": 0x1C, "ORIGIN": 0x32, "CALLER": 0x33, "CALLDATALOAD": 0x35,
    "CALLDATASIZE": 0x36,
    "POP": 0x50, "SLOAD": 0x54, "SSTORE": 0x55, "JUMP": 0x56,
    "JUMPI": 0x57, "JUMPDEST": 0x5B, "RETURN": 0xF3, "REVERT": 0xFD,
    "INVALID": 0xFE,
    "SELFDESTRUCT": 0xFF, "SUICIDE": 0xFF,
}
OPCODES.update({f"PUSH{n}": 0x5F + n for n in range(1, 33)})
OPCODES.update({f"DUP{n}": 0x7F + n for n in range(1, 17)})
OPCODES.update({f"SWAP{n}": 0x8F + n for n in range(1, 17)})
NAMES: Dict[int, str] = {}
for _name, _byte in OPCODES.items():
    NAMES.setdefault(_byte, _name)


# -- assembler (copy of mythril_tpu/support/assembler.py) -------------------

def _push_width(value: int) -> int:
    return max(1, (value.bit_length() + 7) // 8)


def assemble(source: str) -> bytes:
    """One instruction per line or ``;``-separated, ``#`` comments;
    ``PUSH`` picks the smallest width, ``PUSHn`` fixes it; ``label:``
    defines and ``@label`` references (a PUSH2 of the offset)."""
    tokens = []
    for raw_line in source.replace(";", "\n").splitlines():
        line = raw_line.split("#")[0].strip()
        if not line:
            continue
        if line.endswith(":") and " " not in line:
            tokens.append(("label", line[:-1]))
            continue
        parts = line.split()
        tokens.append(("op", (parts[0].upper(),
                              parts[1] if len(parts) > 1 else None)))

    labels: Dict[str, int] = {}
    offset = 0
    layout = []
    for kind, payload in tokens:
        if kind == "label":
            labels[payload] = offset
            continue
        mnem, arg = payload
        if mnem == "PUSH" and arg is not None and arg.startswith("@"):
            layout.append(("pushlabel", arg[1:]))
            offset += 3
        elif mnem == "PUSH" and arg is not None:
            value = int(arg, 0)
            layout.append(("push", (_push_width(value), value)))
            offset += 1 + _push_width(value)
        elif mnem.startswith("PUSH") and arg is not None:
            width, value = int(mnem[4:]), int(arg, 0)
            if value >> (8 * width):
                raise ValueError(f"{mnem} {arg} does not fit")
            layout.append(("push", (width, value)))
            offset += 1 + width
        else:
            if mnem not in OPCODES:
                raise ValueError(f"unknown mnemonic {mnem!r}")
            layout.append(("plain", mnem))
            offset += 1

    out = bytearray()
    for kind, payload in layout:
        if kind == "plain":
            out.append(OPCODES[payload])
        elif kind == "push":
            width, value = payload
            out.append(OPCODES[f"PUSH{width}"])
            out += value.to_bytes(width, "big")
        else:
            if payload not in labels:
                raise ValueError(f"undefined label {payload!r}")
            out.append(OPCODES["PUSH2"])
            out += labels[payload].to_bytes(2, "big")
    return bytes(out)


# -- concrete interpreter ---------------------------------------------------

@dataclass(frozen=True)
class Tx:
    """One message call: 4-byte selector and argument words are packed
    by the generator into ``calldata``."""

    calldata: bytes
    caller: int = ATTACKER
    origin: int = ATTACKER


@dataclass(frozen=True)
class Word:
    """A stack value with its taint: derived from ORIGIN, and the
    addresses of overflowed arithmetic it derives from."""

    value: int
    origin: bool = False
    overflow: FrozenSet[int] = frozenset()


def _join(value: int, *words: Word) -> Word:
    return Word(value & MASK, any(w.origin for w in words),
                frozenset().union(*(w.overflow for w in words)))


@dataclass
class Outcome:
    status: str  # stop | return | revert | invalid | selfdestruct
    events: Set[Tuple[str, int]] = field(default_factory=set)
    pcs: Set[int] = field(default_factory=set)


def jumpdests(code: bytes) -> Set[int]:
    dests, pc = set(), 0
    while pc < len(code):
        op = code[pc]
        if op == OPCODES["JUMPDEST"]:
            dests.add(pc)
        pc += 1 + (op - 0x5F if 0x60 <= op <= 0x7F else 0)
    return dests


def execute(code: bytes, tx: Tx, storage: Dict[int, int],
            attacker_only: bool, step_limit: int = 100_000) -> Outcome:
    """Run ``tx`` against ``code``; ``storage`` is updated in place
    unless the call reverts.  ``attacker_only`` says whether every
    transaction of the sequence so far came from the attacker."""
    dests = jumpdests(code)
    stack: List[Word] = []
    written: Dict[int, int] = {}
    sunk: Set[int] = set()
    out = Outcome("stop")
    pc = 0

    def pop() -> Word:
        if not stack:
            raise RuntimeError(f"stack underflow at {pc}")
        return stack.pop()

    for _ in range(step_limit):
        if pc >= len(code):
            break
        op = code[pc]
        name = NAMES.get(op)
        if name is None:
            raise RuntimeError(f"opcode 0x{op:02x} at {pc} is not modelled")
        out.pcs.add(pc)
        here = pc
        pc += 1
        if name.startswith("PUSH"):
            width = op - 0x5F
            stack.append(Word(int.from_bytes(code[pc:pc + width], "big")))
            pc += width
        elif name.startswith("DUP"):
            stack.append(stack[-(op - 0x7F)])
        elif name.startswith("SWAP"):
            depth = op - 0x8F
            stack[-1], stack[-1 - depth] = stack[-1 - depth], stack[-1]
        elif name in ("ADD", "MUL", "SUB"):
            a, b = pop(), pop()
            true = {"ADD": a.value + b.value, "MUL": a.value * b.value,
                    "SUB": a.value - b.value}[name]
            word = _join(true, a, b)
            if not 0 <= true < WORD:
                word = Word(word.value, word.origin, word.overflow | {here})
            stack.append(word)
        elif name in ("LT", "GT", "EQ", "AND", "SHR"):
            a, b = pop(), pop()
            value = {"LT": int(a.value < b.value),
                     "GT": int(a.value > b.value),
                     "EQ": int(a.value == b.value),
                     "AND": a.value & b.value,
                     "SHR": b.value >> a.value if a.value < 256 else 0}[name]
            stack.append(_join(value, a, b))
        elif name == "ISZERO":
            a = pop()
            stack.append(_join(int(a.value == 0), a))
        elif name == "ORIGIN":
            stack.append(Word(tx.origin, origin=True))
        elif name == "CALLER":
            stack.append(Word(tx.caller))
        elif name == "CALLDATASIZE":
            stack.append(Word(len(tx.calldata)))
        elif name == "CALLDATALOAD":
            offset = pop().value
            chunk = tx.calldata[offset:offset + 32] if offset < 2**32 else b""
            stack.append(Word(int.from_bytes(chunk.ljust(32, b"\0"), "big")))
        elif name == "POP":
            pop()
        elif name == "SLOAD":
            slot = pop().value
            stack.append(Word(written.get(slot, storage.get(slot, 0))))
        elif name == "SSTORE":
            slot, value = pop(), pop()
            sunk |= value.overflow
            written[slot.value] = value.value
        elif name == "JUMP":
            pc = pop().value
            if pc not in dests:
                raise RuntimeError(f"bad jump to {pc} at {here}")
        elif name == "JUMPI":
            dest, cond = pop(), pop()
            if cond.origin:
                out.events.add(("SWC-115", here))
            sunk |= cond.overflow
            if cond.value:
                pc = dest.value
                if pc not in dests:
                    raise RuntimeError(f"bad jump to {pc} at {here}")
        elif name == "JUMPDEST":
            pass
        elif name in ("STOP", "RETURN"):
            if name == "RETURN":
                pop(), pop()
            out.status = name.lower()
            break
        elif name in ("REVERT", "INVALID"):
            # the origin check fires as the JUMPI is reached, the
            # assertion as the INVALID is; the rest of the call is undone
            out.status = name.lower()
            out.events = {e for e in out.events if e[0] == "SWC-115"}
            if name == "INVALID":
                out.events.add(("SWC-110", here))
            return out
        elif name == "SELFDESTRUCT":
            pop()
            if attacker_only:
                out.events.add(("SWC-106", here))
            out.status = "selfdestruct"
            storage.update(written)
            return out
    else:
        raise RuntimeError("step limit reached")
    storage.update(written)
    out.events |= {("SWC-101", address) for address in sunk}
    return out


def run_sequence(code: bytes, txs) -> Tuple[Set[Tuple[str, int]], Set[int]]:
    """Events and executed addresses of a sequence of transactions,
    from empty storage; a sequence stops at a SELFDESTRUCT."""
    storage: Dict[int, int] = {}
    events: Set[Tuple[str, int]] = set()
    pcs: Set[int] = set()
    attacker_only = True
    for tx in txs:
        attacker_only &= tx.caller == tx.origin == ATTACKER
        outcome = execute(code, tx, storage, attacker_only)
        events |= outcome.events
        pcs |= outcome.pcs
        if outcome.status == "selfdestruct":
            break
    return events, pcs
