"""Concrete exploit-sequence validation on the lockstep batched VM.

Every Issue carries a solver-concretized transaction sequence
(analysis/solver.py:get_transaction_sequence).  This module replays
those sequences through the SoA lockstep interpreter (ops/lockstep.py)
against the contract's runtime bytecode: storage effects are carried
across transactions, and a replay that halts at the flagged program
counter on a host-service opcode (SELFDESTRUCT, the CALL family,
INVALID, SHA3, ...) is concrete evidence the exploit path executes.

The reference has no counterpart — it trusts z3 models unconditionally
(reference mythril/analysis/solver.py:48 returns the sequence as-is).
Here the solver stack is ours, so issues gain an independent,
bit-exact confirmation layer that runs the whole issue batch through
one compiled device program.

Statuses (stored on ``issue.concrete_replay``, logged, never
serialized into reports — report formats stay reference-identical):

- ``confirmed``: some transaction halted exactly at ``issue.address``
  needing a host service — the flagged opcode was concretely reached.
- ``executed``: the sequence ran to clean halts without touching the
  flagged address (common for control-flow findings whose trigger is a
  JUMPI the lockstep VM executes without stopping).
- ``unsupported``: the replay left the lockstep regime (creation
  steps, oversized state, device unavailable).
"""

import logging
from typing import List, Optional

import numpy as np

from mythril_tpu.observability import spans as obs

log = logging.getLogger(__name__)

MAX_REPLAY_STEPS = 65536


def _hex_int(text, default=0) -> int:
    if text in (None, "", "0x"):
        return default
    return int(text, 16)


def _word_limbs(value: int) -> np.ndarray:
    from mythril_tpu.ops.u256 import from_int

    return np.asarray(from_int(value))


def replay_issue(issue, runtime_code: bytes) -> Optional[str]:
    """Replay one issue's concrete transaction sequence; see module
    docstring for the status contract."""
    from mythril_tpu.ops import lockstep

    sequence = getattr(issue, "transaction_sequence", None)
    if not sequence or not isinstance(sequence, dict):
        return None
    steps = sequence.get("steps") or []
    if not steps or not runtime_code:
        return None

    skeys = svals = None
    used = 0
    for step in steps:
        if not step.get("address"):
            return "unsupported"  # creation step: different code object
        calldata = bytes.fromhex(step.get("input", "0x")[2:])
        caller = _hex_int(step.get("origin"))
        value = _hex_int(step.get("value"))

        # a concrete run on the device: its uploads and its result can
        # queue behind a kernel already running there (a prefetch)
        with obs.wait("replay.upload"):
            state = lockstep.init_state(
                1,
                np.asarray([list(calldata)], np.uint8).reshape(
                    1, len(calldata)),
                np.asarray([len(calldata)], np.int32),
                callvalue=_word_limbs(value)[None, :],
                caller=_word_limbs(caller)[None, :],
                storage_keys=skeys,
                storage_vals=svals,
            )
            try:
                final, _ = lockstep.run_batch(
                    runtime_code, state, MAX_REPLAY_STEPS
                )
            except Exception as e:  # noqa: BLE001 — validation must not fail analysis
                log.debug("lockstep replay unavailable: %s", e)
                return None

        with obs.wait("replay.fetch"):
            halt = int(np.asarray(final.halt)[0])
            pc = int(np.asarray(final.pc)[0])
        if halt == lockstep.RUNNING:
            return "unsupported"  # step cap exhausted mid-transaction
        if halt == lockstep.NEEDS_HOST:
            if pc == issue.address:
                return "confirmed"
            return "unsupported"  # left the lockstep regime elsewhere
        if halt == lockstep.ERROR:
            # assert-style findings flag the INVALID/ASSERT_FAIL opcode;
            # a genuine VM error at that pc is the expected outcome
            return "confirmed" if pc == issue.address else "executed"

        # carry storage into the next transaction (revert discards)
        if halt != lockstep.REVERTED:
            sused = np.asarray(final.sused)[0]
            used = int(sused.sum())
            if used:
                order = np.nonzero(sused)[0]
                skeys = np.asarray(final.skeys)[:, order, :]
                svals = np.asarray(final.svals)[:, order, :]
    return "executed"


def replay_issues(issues: List, runtime_code_hex: str) -> None:
    """Annotate each issue with its replay status (best-effort)."""
    from mythril_tpu.ops.device_health import count_skip, device_ok

    if not device_ok():
        count_skip("concrete replay")
        return
    try:
        code = bytes.fromhex(runtime_code_hex.removeprefix("0x"))
    except ValueError:
        return
    confirmed = 0
    for issue in issues:
        status = replay_issue(issue, code)
        issue.concrete_replay = status
        if status == "confirmed":
            confirmed += 1
    if issues:
        log.info(
            "Concrete replay: %d/%d issues confirmed on-device",
            confirmed,
            len(issues),
        )
