"""Concrete-prefix dispatcher pre-split (SURVEY §7.2.1, first step).

The reference's worklist symbolically executes the function dispatcher
for EVERY open state of EVERY transaction round: the selector-compare
chain forks at each JUMPI and each fork pays a feasibility check
(reference mythril/laser/ethereum/svm.py:221-265 — the loop being
displaced).  But the dispatcher prefix is pure calldata logic — it
reads no storage and no environment beyond the calldata word — so its
branch structure is IDENTICAL for every open state and every
transaction: one selector per function entry plus a fallback.

This module splits the frontier by selector BEFORE symbolic execution
starts:

1. **match** — the disassembly's instruction list is checked against
   the canonical dispatcher shape (``PUSH 0; CALLDATALOAD; PUSH 0xE0;
   SHR`` prelude, then ``DUP1; PUSH4 h; EQ; PUSH entry; JUMPI`` per
   function).  Anything else — legacy DIV dispatchers, calldatasize
   guards, hand-rolled dispatch — declines, and the state executes the
   prefix symbolically as before (no behavior change);
2. **validate** — the SoA lockstep interpreter (ops/lockstep.py)
   concretely executes one lane per selector and the mapped entry's
   visited-pc bit must be set: the static match is cross-checked
   against real execution on the batched VM (cached per bytecode);
3. **split** — each transaction seed is replaced by one state per
   selector, positioned AT the function entry with the dispatcher's
   exact machine effects reproduced symbolically (selector word on the
   stack, ``LShR(calldata[0..31], 0xE0) == h`` constraint, the linear
   prefix's min/max gas and instruction depth), plus the fallback
   state behind the negated selector disjunction.

The per-selector states are exactly the states symbolic execution
would have produced at those program points, so findings are
unchanged; the dispatcher's JUMPI forks, their per-fork feasibility
checks, and the per-state prefix re-execution are skipped.  Telemetry:
``dispatch_stats.presplit_states`` counts seeded states, so the bench
can attribute the state-count/wall effect.
"""

import logging
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from mythril_tpu.observability import spans as obs

log = logging.getLogger(__name__)

MAX_SELECTORS = 64
# validation probes must cover the longest prefix (4 + 5*MAX_SELECTORS
# steps to the deepest entry) plus a margin; the visited bit lands on
# the loop iteration AFTER the jump executes
VALIDATE_STEPS = 4 + 5 * MAX_SELECTORS + 64


class DispatcherPlan(NamedTuple):
    """A validated dispatch prefix, cached per bytecode."""

    # selector -> (entry byte addr, entry instr index, gas_min,
    #              gas_max, depth_delta) — depth counts taken/observed
    #              jumps, matching jumpi_'s accounting, NOT instructions
    branches: Dict[int, tuple]
    # fallback: (instr index after last JUMPI, gas_min, gas_max,
    #            depth_delta)
    fallback: tuple


_plan_cache: Dict[str, Optional[DispatcherPlan]] = {}


def _push_value(instr) -> Optional[int]:
    if not instr.op_code.startswith("PUSH") or instr.argument is None:
        return None
    return int.from_bytes(instr.argument, "big")


def _match_dispatcher(disassembly) -> Optional[DispatcherPlan]:
    """Static shape match; returns the plan or None (decline)."""
    from mythril_tpu.support.opcodes import get_opcode_gas

    instrs = disassembly.instruction_list
    if len(instrs) < 9:
        return None
    # prelude: PUSH 0; CALLDATALOAD; PUSH 0xE0; SHR
    if not (
        _push_value(instrs[0]) == 0
        and instrs[1].op_code == "CALLDATALOAD"
        and _push_value(instrs[2]) == 0xE0
        and instrs[3].op_code == "SHR"
    ):
        return None
    gas_min = gas_max = 0
    for instr in instrs[:4]:
        lo, hi = get_opcode_gas(instr.op_code)
        gas_min += lo
        gas_max += hi
    branches: Dict[int, tuple] = {}
    index = 4
    blocks = 0
    while (
        index + 4 < len(instrs)
        and instrs[index].op_code == "DUP1"
        and instrs[index + 1].op_code == "PUSH4"
        and instrs[index + 2].op_code == "EQ"
        and instrs[index + 3].op_code.startswith("PUSH")
        and instrs[index + 4].op_code == "JUMPI"
    ):
        selector = _push_value(instrs[index + 1])
        entry = _push_value(instrs[index + 3])
        if selector is None or entry is None or selector in branches:
            return None
        for instr in instrs[index : index + 5]:
            lo, hi = get_opcode_gas(instr.op_code)
            gas_min += lo
            gas_max += hi
        blocks += 1
        entry_index = disassembly.address_to_index.get(entry) if hasattr(
            disassembly, "address_to_index"
        ) else None
        if entry_index is None:
            # resolve byte address -> instruction index
            entry_index = next(
                (
                    i for i, ins in enumerate(instrs)
                    if ins.address == entry
                ),
                None,
            )
        if (
            entry_index is None
            or instrs[entry_index].op_code != "JUMPDEST"
        ):
            return None
        # mstate.depth counts jumps (jumpi_ increments both fork
        # arms), so a branch taken at block i passed i untaken JUMPIs
        # plus its own taken one
        branches[selector] = (entry, entry_index, gas_min, gas_max, blocks)
        index += 5
    if not branches or len(branches) > MAX_SELECTORS:
        return None
    return DispatcherPlan(
        branches=branches,
        fallback=(index, gas_min, gas_max, blocks),
    )


def _validate_on_lockstep(code_hex: str, plan: DispatcherPlan):
    """One concrete lane per selector through the SoA interpreter; the
    mapped entry's visited-pc bit must be set for every lane.  Returns
    True/False for a real verdict, or None when validation could not
    run (demoted device) — the caller must NOT cache None-by-health."""
    from mythril_tpu.ops import lockstep
    from mythril_tpu.ops.device_health import count_skip, device_ok

    if not device_ok():
        count_skip("lockstep pre-split validation")
        return None
    try:
        code = bytes.fromhex(code_hex.removeprefix("0x"))
        selectors = sorted(plan.branches)
        batch = len(selectors)
        calldata = np.zeros((batch, 36), np.uint8)
        for lane, selector in enumerate(selectors):
            calldata[lane, :4] = list(selector.to_bytes(4, "big"))
        # a concrete run on the device: its uploads and its result
        # can queue behind a kernel already running there (a prefetch)
        with obs.wait("lockstep.upload"):
            state = lockstep.init_state(
                batch, calldata, np.full(batch, 36, np.int32)
            )
            _final, visited, _steps = lockstep.run_batch(
                code, state, max_steps=VALIDATE_STEPS, record_visited=True
            )
        with obs.wait("lockstep.fetch"):
            visited = np.asarray(visited)
        return all(
            lockstep.pc_visited(visited, lane, plan.branches[sel][0])
            for lane, sel in enumerate(selectors)
        )
    except Exception:  # noqa: BLE001 — validation failure just declines
        log.debug("lockstep dispatcher validation failed", exc_info=True)
        return None


def dispatcher_plan(disassembly) -> Optional[DispatcherPlan]:
    """Matched + lockstep-validated plan for this bytecode, or None."""
    code_hex = disassembly.bytecode if isinstance(
        disassembly.bytecode, str
    ) else ""
    if not code_hex:
        return None
    cached = _plan_cache.get(code_hex, False)
    if cached is not False:
        return cached
    plan = _match_dispatcher(disassembly)
    if plan is not None:
        verdict = _validate_on_lockstep(code_hex, plan)
        if verdict is None:
            return None  # transient (device health): do NOT cache
        if not verdict:
            plan = None
    if len(_plan_cache) > 64:
        _plan_cache.clear()
    _plan_cache[code_hex] = plan
    return plan


def presplit_states(global_state) -> Optional[List]:
    """Per-selector copies of a transaction seed, positioned at the
    validated function entries; None when the pre-split declines."""
    from mythril_tpu.smt import LShR, symbol_factory
    from mythril_tpu.support.support_args import args

    if not getattr(args, "lockstep_dispatch", False):
        return None
    environment = global_state.environment
    if global_state.mstate.pc != 0 or global_state.mstate.stack:
        return None
    plan = dispatcher_plan(environment.code)
    if plan is None:
        return None

    # the dispatcher's own selector computation, built with the same
    # primitives the symbolic instructions would use
    word = environment.calldata.get_word_at(
        symbol_factory.BitVecVal(0, 256)
    )
    selector_word = LShR(word, symbol_factory.BitVecVal(0xE0, 256))

    split = []
    for selector, (entry, entry_index, gmin, gmax, depth_delta) in sorted(
        plan.branches.items()
    ):
        state = global_state.__copy__()
        condition = selector_word == symbol_factory.BitVecVal(
            selector, 256
        )
        state.world_state.constraints.append(condition)
        state.mstate.pc = entry_index
        state.mstate.stack.append(selector_word)
        state.mstate.min_gas_used += gmin
        state.mstate.max_gas_used += gmax
        state.mstate.depth += depth_delta
        split.append((state, condition))
    # fallback: no selector matched; execution continues after the chain
    fb_index, gmin, gmax, depth_delta = plan.fallback
    state = global_state.__copy__()
    from mythril_tpu.smt import And

    condition = None
    for selector in plan.branches:
        clause = selector_word != symbol_factory.BitVecVal(selector, 256)
        condition = clause if condition is None else And(condition, clause)
    state.world_state.constraints.append(condition)
    state.mstate.pc = fb_index
    state.mstate.stack.append(selector_word)
    state.mstate.min_gas_used += gmin
    state.mstate.max_gas_used += gmax
    state.mstate.depth += depth_delta
    split.append((state, condition))
    return split
