"""Durable checkpoint/resume plane + graceful drain.

A production analysis gets preempted, OOM-killed, and rescheduled; a
``-t 3`` run that dies at minute 2 of 3 used to lose everything — the
LASER frontier, the probe memo, the nogood DB, and every finding the
callback modules had already confirmed.  This module makes the analysis
itself a recoverable work unit:

- **Journal**: an atomic, versioned, CRC-checked snapshot written under
  ``--checkpoint-dir`` (tmp + rename; the last two generations are
  retained so a crash mid-rename can never leave zero valid journals).
  Each generation holds the exploration frontier (open world-states at
  the last transaction boundary + the transaction index), the confirmed
  detection-module findings, the verdict-preserving solver channels
  from ``smt/bitblast.py`` (permanent UNSAT memo, SAT probe memo,
  recent models), the cached device-health verdict, and the dispatch /
  resilience telemetry.

- **Cadence**: a boundary snapshot is written before every transaction
  of ``LaserEVM._execute_transactions``; between boundaries the journal
  is refreshed (same frontier, fresh channels/stats) every
  ``MYTHRIL_TPU_CHECKPOINT_PERIOD`` seconds (default 30; ``0`` means
  every scheduler round — tests use that) and after every
  degradation-ladder demotion (:func:`note_demotion`).

- **Resume**: ``myth analyze --resume <dir>`` (or
  ``args.resume_from``) rebuilds the frontier from the newest valid
  generation and continues from the interrupted transaction.  The
  restored channels re-decide the already-explored prefix from memo
  hits, so kill-at-any-fault-point + resume yields findings identical
  to an uninterrupted run — re-execution of the interrupted transaction
  regenerates exactly its findings (boundary-consistent frontier +
  findings pairs make double-reporting structurally impossible).

- **Drain**: SIGTERM/SIGINT set a cooperative drain flag that every
  long loop polls (the scheduler round loop in ``laser/ethereum/svm``,
  the round ladders in ``ops/batched_sat.py`` / ``ops/pallas_prop.py``
  between budgeted rounds).  In-flight rounds land or are abandoned to
  the CDCL tail, a final checkpoint is written, and the report ships
  with ``meta.resilience.partial: true`` instead of the process dying
  mid-dispatch.  A second signal force-exits.

Serialization: world-states and findings pickle through custom
reducers — term-DAG nodes re-intern on load (structural identity is
restored in the new process, with fresh node ids), account
balance-closures are rebuilt.  Channels keyed by node *id* (memo keys,
EvalEnv tables) are frozen to node-object form before pickling and
thawed back to the resumed process's ids, because ids are an artifact
of interning order and never survive a process boundary.
"""

import copyreg
import logging
import os
import pickle
import signal
import struct
import threading
import time
import zlib
from copy import copy
from typing import Dict, List, Optional

from mythril_tpu.resilience.telemetry import resilience_stats

log = logging.getLogger(__name__)

JOURNAL_MAGIC = b"MTPUCKPT"
JOURNAL_VERSION = 1
JOURNAL_KEEP = 2          # generations retained (tmp+rename + last-two
#                           retention: one corrupt tail never strands a run)
DEFAULT_PERIOD_S = 30.0


class JournalCorrupt(RuntimeError):
    """Every retained journal generation failed validation (bad magic,
    version mismatch, CRC mismatch, or truncated body)."""


def checkpoint_period_s() -> float:
    """Journal refresh cadence: ``MYTHRIL_TPU_CHECKPOINT_PERIOD``
    seconds (0 = refresh every scheduler round — chaos tests), default
    30 s — cheap enough to be invisible in bench headlines
    (``checkpoint_overhead_s`` gates regressions) while bounding lost
    work to one cadence window."""
    try:
        return max(
            0.0,
            float(os.environ.get("MYTHRIL_TPU_CHECKPOINT_PERIOD",
                                 DEFAULT_PERIOD_S)),
        )
    except ValueError:
        return DEFAULT_PERIOD_S


# ---------------------------------------------------------------------------
# pickle reducers: term nodes re-intern, balance closures rebuild
# ---------------------------------------------------------------------------


def _reintern_node(op, args, params, width, sort):
    from mythril_tpu.smt import terms as T

    return T._I.get(op, args, params, width, sort)


def _reduce_node(node):
    # args unpickle (and re-intern) bottom-up before the outer call runs,
    # so structural sharing and TRUE/FALSE identity survive the process
    # boundary; ids are reassigned by the resumed interner
    return _reintern_node, (
        node.op, node.args, node.params, node.width, node.sort,
    )


def _rebuild_account(state):
    from mythril_tpu.laser.ethereum.state.account import Account

    account = Account.__new__(Account)
    account.__dict__.update(state)
    account.balance = lambda: account._balances[account.address]
    return account


def _reduce_account(account):
    state = dict(account.__dict__)
    state.pop("balance", None)  # per-instance closure: rebuilt on load
    return _rebuild_account, (state,)


def _rebuild_storage(state):
    from mythril_tpu.laser.ethereum.state.account import Storage

    storage = Storage.__new__(Storage)
    storage.__dict__.update(state)
    storage.dynld = None  # a live RPC client never crosses the journal
    return storage


def _reduce_storage(storage):
    state = dict(storage.__dict__)
    state["dynld"] = None
    return _rebuild_storage, (state,)


_reducers_installed = False


def _install_reducers() -> None:
    global _reducers_installed
    if _reducers_installed:
        return
    from mythril_tpu.laser.ethereum.state.account import Account, Storage
    from mythril_tpu.smt import terms as T

    copyreg.pickle(T.Node, _reduce_node)
    copyreg.pickle(Account, _reduce_account)
    copyreg.pickle(Storage, _reduce_storage)
    _reducers_installed = True


# ---------------------------------------------------------------------------
# channel freeze/thaw: node-id keys -> node objects -> resumed ids
# ---------------------------------------------------------------------------


def _id_to_node() -> Dict[int, object]:
    from mythril_tpu.smt import terms as T

    return {node.id: node for node in T._I.table.values()}


def _freeze_env(env, id2node):
    """EvalEnv -> journal form with node-object keys (drops the
    id-keyed persistent evaluation memo — it is a cache and its keys
    would be stale in the resumed process)."""
    variables = [
        (id2node[k], v) for k, v in env.variables.items() if k in id2node
    ]
    arrays = [
        (id2node[k], dict(v)) for k, v in env.arrays.items() if k in id2node
    ]
    ufs = [
        (id2node[fid], argvals, v)
        for (fid, argvals), v in env.ufs.items()
        if fid in id2node
    ]
    return {
        "variables": variables,
        "arrays": arrays,
        "ufs": ufs,
        "array_default": env.array_default,
    }


def _thaw_env(frozen):
    from mythril_tpu.smt import terms as T

    return T.EvalEnv(
        variables={n.id: v for n, v in frozen["variables"]},
        arrays={n.id: dict(v) for n, v in frozen["arrays"]},
        ufs={(n.id, argvals): v for n, argvals, v in frozen["ufs"]},
        array_default=frozen["array_default"],
    )


def freeze_channels(ctx) -> dict:
    """Capture the verdict-preserving solver channels of a
    BlastContext in journal form: the permanent UNSAT memo, the SAT
    half of the probe memo (negative probes are model-version-scoped
    and would be stale), and the recent-model set.  Literal-level state
    (CNF pool, device nogoods) is derived and deliberately NOT
    journaled — literal numbering is an artifact of blast order; the
    resumed analysis re-derives it and re-learns nogoods as the memo
    hits re-refute."""
    from mythril_tpu.smt import terms as T

    id2node = _id_to_node()

    def nodes_of(key):
        nodes = tuple(id2node.get(i) for i in key)
        return None if any(n is None for n in nodes) else nodes

    unsat_sets = [
        nodes for key in ctx.unsat_memo for nodes in (nodes_of(key),)
        if nodes is not None
    ]
    probe_sat = [
        (nodes, _freeze_env(env, id2node))
        for key, env in ctx.probe_memo.items()
        if isinstance(env, T.EvalEnv)
        for nodes in (nodes_of(key),)
        if nodes is not None
    ]
    models = [_freeze_env(env, id2node) for env in ctx.recent_models]
    return {"unsat_sets": unsat_sets, "probe_sat": probe_sat,
            "models": models}


def thaw_channels(ctx, channels: dict) -> None:
    """Seed a fresh BlastContext with journaled channels (keys rebuilt
    from the re-interned nodes' new ids)."""
    for nodes in channels.get("unsat_sets", ()):
        ctx.unsat_memo[tuple(sorted(n.id for n in nodes))] = True
    for nodes, frozen in channels.get("probe_sat", ()):
        ctx.probe_memo[
            tuple(sorted(n.id for n in nodes))
        ] = _thaw_env(frozen)
    ctx.recent_models = [
        _thaw_env(frozen) for frozen in channels.get("models", ())
    ]
    if ctx.recent_models:
        ctx.model_version += 1


# ---------------------------------------------------------------------------
# journal file format: MAGIC | version u32 | crc32 u32 | len u64 | body
# ---------------------------------------------------------------------------

_HEADER = struct.Struct("<II Q")


def write_journal(directory: str, payload: dict) -> str:
    """Atomically persist one journal generation; returns its path.
    tmp + fsync + rename, then prune to the last JOURNAL_KEEP
    generations (never the one just written)."""
    _install_reducers()
    os.makedirs(directory, exist_ok=True)
    body = pickle.dumps(payload, protocol=4)
    header = JOURNAL_MAGIC + _HEADER.pack(
        JOURNAL_VERSION, zlib.crc32(body), len(body)
    )
    generation = 1 + max(
        (g for g, _ in _generations(directory)), default=0
    )
    final = os.path.join(directory, f"ckpt-{generation:08d}.bin")
    tmp = os.path.join(directory, ".journal.tmp")
    with open(tmp, "wb") as fh:
        fh.write(header)
        fh.write(body)
        fh.flush()
        os.fsync(fh.fileno())
    os.rename(tmp, final)
    for _, stale in _generations(directory)[:-JOURNAL_KEEP]:
        try:
            os.unlink(stale)
        except OSError:
            pass
    return final


def _generations(directory: str):
    """[(generation, path)] ascending."""
    out = []
    try:
        names = os.listdir(directory)
    except OSError:
        return out
    for name in names:
        if name.startswith("ckpt-") and name.endswith(".bin"):
            try:
                out.append((int(name[5:-4]), os.path.join(directory, name)))
            except ValueError:
                continue
    return sorted(out)


def _read_one(path: str) -> dict:
    with open(path, "rb") as fh:
        raw = fh.read()
    if raw[: len(JOURNAL_MAGIC)] != JOURNAL_MAGIC:
        raise JournalCorrupt(f"{path}: bad magic")
    version, crc, length = _HEADER.unpack_from(raw, len(JOURNAL_MAGIC))
    if version != JOURNAL_VERSION:
        raise JournalCorrupt(
            f"{path}: journal version {version} != {JOURNAL_VERSION}"
        )
    body = raw[len(JOURNAL_MAGIC) + _HEADER.size:]
    if len(body) != length:
        raise JournalCorrupt(f"{path}: truncated body "
                             f"({len(body)} != {length})")
    if zlib.crc32(body) != crc:
        raise JournalCorrupt(f"{path}: CRC mismatch")
    _install_reducers()
    return pickle.loads(body)


def load_journal(directory: str) -> Optional[dict]:
    """Newest valid journal generation, or None when the directory
    holds none (a kill before the first boundary).  Falls back one
    generation on corruption (that is what the second retained
    generation is for); raises :class:`JournalCorrupt` only when every
    generation failed validation — resuming from garbage must be loud,
    not silently fresh."""
    generations = _generations(directory)
    if not generations:
        return None
    errors = []
    for _, path in reversed(generations):
        try:
            payload = _read_one(path)
        except JournalCorrupt as exc:
            errors.append(str(exc))
            _note_corrupt_fallback(path, str(exc))
        except Exception as exc:  # noqa: BLE001 — unpickle failure
            errors.append(f"{path}: {exc}")
            _note_corrupt_fallback(path, str(exc))
        else:
            if errors:
                log.warning(
                    "checkpoint: resumed from an OLDER generation after "
                    "%d corrupt one(s) — up to one cadence window of "
                    "work will be re-executed", len(errors),
                )
            return payload
    raise JournalCorrupt("; ".join(errors))


def _note_corrupt_fallback(path: str, why: str) -> None:
    """One skipped-as-corrupt journal generation: loud, structured,
    counted.  The run survives on an older generation (that is what
    retention is for), but a silently rotting journal directory is an
    operator problem, not a log-greppable footnote."""
    resilience_stats.checkpoint_corrupt_fallbacks += 1
    log.warning("checkpoint: skipping corrupt journal %s (%s)", path, why)
    try:
        from mythril_tpu.observability import spans as obs

        obs.instant("checkpoint.corrupt_fallback", cat="resilience",
                    path=os.path.basename(path), error=why)
    except Exception:  # noqa: BLE001 — telemetry never blocks a resume
        pass


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

_drain_event = threading.Event()
_handlers_installed = False


def drain_requested() -> bool:
    """True when the run should wind down at the next cooperative
    boundary: the process-wide drain flag is set (SIGTERM / SIGINT),
    the current request's wall-clock budget has expired
    (``resilience/budget.py`` — the serve plane's per-request deadline,
    which clears between requests), or the resource governor escalated
    to its terminal ``drain_partial`` rung (``resilience/governor.py``
    — a breached state/term/lane/RSS budget, which clears per
    contract).  All causes walk the exact same boundaries: the svm
    loops, the dispatch gate, and the device round ladders."""
    if _drain_event.is_set():
        return True
    from mythril_tpu.resilience.budget import budget_expired

    if budget_expired():
        return True
    from mythril_tpu.resilience.governor import drain_rung_active

    return drain_rung_active()


def request_drain(reason: str = "signal") -> None:
    if not _drain_event.is_set():
        log.warning(
            "drain requested (%s): finishing in-flight rounds, writing a "
            "final checkpoint, and emitting a partial report", reason,
        )
        from mythril_tpu.observability import flight as obs_flight
        from mythril_tpu.observability import spans as obs

        obs.instant("drain.requested", cat="resilience", reason=reason)
        obs_flight.get_flight_recorder().dump("drain")
        # flush the --trace-out / --metrics-out artifacts NOW, not only
        # at process exit: a drain that wedges (and eats the second,
        # force-kill signal) or a consumer that never reaches the
        # normal finalize path used to lose the whole timeline — the
        # one artifact that explains the drain.  finalize_outputs is
        # idempotent and never raises; the end-of-run flush simply
        # rewrites the files with the complete timeline.
        try:
            from mythril_tpu.observability import finalize_outputs

            finalize_outputs()
        except Exception:  # noqa: BLE001 — flushing must not stall drain
            log.debug("drain-time artifact flush failed", exc_info=True)
    _drain_event.set()


def install_signal_handlers() -> None:
    """SIGTERM/SIGINT -> cooperative drain; a second signal restores
    the default disposition so a wedged drain can still be killed.
    Main-thread only (signal module restriction); safe to call twice."""
    global _handlers_installed
    if _handlers_installed:
        return
    if threading.current_thread() is not threading.main_thread():
        return

    def _on_signal(signum, frame):
        # second-signal detection keys on the signal-driven flag ONLY:
        # an expired per-request budget also makes drain_requested()
        # true, and the first SIGTERM of a budget-expired run must
        # still drain gracefully, not force-exit
        if _drain_event.is_set():
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        request_drain(signal.Signals(signum).name)

    for signum in (signal.SIGTERM, signal.SIGINT):
        signal.signal(signum, _on_signal)
    _handlers_installed = True


# ---------------------------------------------------------------------------
# the plane
# ---------------------------------------------------------------------------


class CheckpointPlane:
    """Per-process checkpoint orchestration.

    ``_execute_transactions`` calls :meth:`restore_transactions` once
    and :meth:`transaction_boundary` per transaction; the scheduler
    round loop calls :meth:`tick`.  Everything no-ops unless a
    checkpoint directory is configured (explicitly or through
    ``args.checkpoint_dir`` / ``args.resume_from``)."""

    def __init__(self):
        self._dir: Optional[str] = None
        self._resume = False
        self._restored: Optional[dict] = None
        self._restore_consumed = False
        self._boundary: Optional[dict] = None
        self._last_write = 0.0
        self._demotion_pending = False
        self.partial = False

    # -- configuration -------------------------------------------------

    def configure(self, directory: Optional[str],
                  resume: bool = False) -> None:
        self._dir = directory
        self._resume = resume
        self._restored = None
        self._restore_consumed = False

    def _pull_args(self) -> None:
        """Late-bind to the args bus: the CLI/analyzer set
        checkpoint_dir / resume_from there before laser runs."""
        if self._dir is not None:
            return
        from mythril_tpu.support.support_args import args

        resume_from = getattr(args, "resume_from", None)
        directory = getattr(args, "checkpoint_dir", None) or resume_from
        if directory:
            self.configure(directory, resume=bool(resume_from))

    @property
    def active(self) -> bool:
        self._pull_args()
        return self._dir is not None

    # -- snapshot assembly ---------------------------------------------

    @staticmethod
    def _frontier_snapshot(open_states) -> list:
        """Private copies of the open world-states, CFG references
        stripped (the statespace of completed transactions is
        rebuilt-from-empty on resume; all detection modules are
        CALLBACK so findings do not depend on it)."""
        snapshot = []
        for world_state in open_states:
            ws = copy(world_state)
            ws.node = None
            snapshot.append(ws)
        return snapshot

    @staticmethod
    def _findings_snapshot() -> dict:
        from mythril_tpu.analysis.module.loader import ModuleLoader

        findings, caches = {}, {}
        for module in ModuleLoader().get_detection_modules():
            name = type(module).__name__
            findings[name] = list(module.issues)
            caches[name] = set(module.cache)
        return {"issues": findings, "caches": caches}

    def _payload(self) -> dict:
        from mythril_tpu.ops import device_health
        from mythril_tpu.ops.batched_sat import dispatch_stats
        from mythril_tpu.smt.solver import get_blast_context

        payload = dict(self._boundary)
        payload["channels"] = freeze_channels(get_blast_context())
        payload["device_demoted"] = device_health.demotion()
        payload["stats"] = {
            "dispatch": {
                k: v for k, v in dispatch_stats.__dict__.items()
                if isinstance(v, (int, float, bool))
            },
            "resilience": resilience_stats.as_dict(),
        }
        payload["partial"] = self.partial
        return payload

    def _write(self) -> None:
        began = time.monotonic()
        try:
            write_journal(self._dir, self._payload())
        except Exception as exc:  # noqa: BLE001 — a full disk must not
            #                       kill the analysis it exists to save
            log.error("checkpoint write failed: %s", exc)
            return
        elapsed = time.monotonic() - began
        resilience_stats.checkpoints_written += 1
        resilience_stats.checkpoint_s += elapsed
        from mythril_tpu.observability import spans as obs

        obs.instant("checkpoint.write", cat="resilience",
                    elapsed_ms=round(elapsed * 1e3, 3))
        self._last_write = time.monotonic()
        self._demotion_pending = False

    # -- hooks ----------------------------------------------------------

    def transaction_boundary(self, laser, address: int,
                             tx_index: int) -> None:
        """Snapshot the boundary state (transactions < tx_index are
        complete; open_states is the pruned frontier tx_index will run
        from) and write a journal generation."""
        if not self.active:
            return
        self._boundary = {
            "kind": "mythril-tpu-checkpoint",
            "address": int(address),
            "tx_index": int(tx_index),
            "transaction_count": int(laser.transaction_count),
            "open_states": self._frontier_snapshot(laser.open_states),
            "findings": self._findings_snapshot(),
        }
        self._write()

    def tick(self) -> None:
        """Periodic refresh from the scheduler round loop: same
        boundary frontier + findings, fresh channels/stats.  Fires on
        the cadence window or immediately after a degradation-ladder
        demotion flagged by :func:`note_demotion`."""
        if not self.active or self._boundary is None:
            return
        if not self._demotion_pending and (
            time.monotonic() - self._last_write < checkpoint_period_s()
        ):
            return
        self._write()

    def note_demotion(self) -> None:
        """Called by the escalation ladder on every demotion: the next
        tick writes a fresh generation regardless of cadence (a
        degrading run is exactly the run about to be preempted)."""
        self._demotion_pending = True

    def finalize(self, partial: bool = False) -> None:
        """Last journal of the run (drain or completion)."""
        self.partial = self.partial or partial
        if self.active and self._boundary is not None:
            self._write()

    # -- resume ---------------------------------------------------------

    def restore_transactions(self, laser, address: int) -> int:
        """When resuming: rebuild the frontier and findings from the
        journal and return the transaction index to continue from.
        Returns 0 (fresh start) when not resuming, no journal exists,
        or the journal describes a different analysis target."""
        if not self.active or not self._resume or self._restore_consumed:
            return 0
        self._restore_consumed = True
        payload = load_journal(self._dir)
        if payload is None:
            log.warning("checkpoint: --resume with an empty journal "
                        "directory; starting fresh")
            return 0
        if payload.get("address") != int(address) or (
            payload.get("transaction_count") != laser.transaction_count
        ):
            log.warning(
                "checkpoint: journal targets address %s / %s txs, not "
                "%s / %s — starting fresh",
                payload.get("address"), payload.get("transaction_count"),
                int(address), laser.transaction_count,
            )
            return 0
        from mythril_tpu.analysis.module.loader import ModuleLoader
        from mythril_tpu.ops import device_health
        from mythril_tpu.smt.solver import get_blast_context

        laser.open_states = list(payload["open_states"])
        findings = payload.get("findings", {})
        for module in ModuleLoader().get_detection_modules():
            name = type(module).__name__
            if name in findings.get("issues", {}):
                module.issues = list(findings["issues"][name])
            if name in findings.get("caches", {}):
                module.cache = set(findings["caches"][name])
        thaw_channels(get_blast_context(), payload.get("channels", {}))
        # device-resident state never survives a resume: the resumed
        # process re-interns nodes and re-blasts literals, so a pool or
        # cone layout uploaded before the journal describes clause
        # indices that no longer exist — drop them all
        from mythril_tpu.ops.batched_sat import reset_resident_pools

        reset_resident_pools()
        if payload.get("device_demoted"):
            device_health.mark_unhealthy(payload["device_demoted"])
        resumed_stats = payload.get("stats", {}).get("resilience", {})
        for key, value in resumed_stats.items():
            if hasattr(resilience_stats, key):
                setattr(resilience_stats, key, value)
        resilience_stats.resumes += 1
        # the restored boundary becomes this run's refresh template
        self._boundary = {
            k: payload[k]
            for k in ("kind", "address", "tx_index", "transaction_count",
                      "open_states", "findings")
        }
        log.info(
            "checkpoint: resumed at transaction %d/%d with %d open "
            "states, %d memoized UNSAT sets",
            payload["tx_index"], payload["transaction_count"],
            len(laser.open_states),
            len(payload.get("channels", {}).get("unsat_sets", ())),
        )
        return int(payload["tx_index"])


_plane: Optional[CheckpointPlane] = None


def get_checkpoint_plane() -> CheckpointPlane:
    global _plane
    if _plane is None:
        _plane = CheckpointPlane()
    return _plane


def reset_for_tests() -> None:
    global _plane
    _plane = None
    _drain_event.clear()
