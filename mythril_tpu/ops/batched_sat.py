"""Batched SAT over a shared clause pool — the TPU solving core.

Design (idiomatic XLA, no data-dependent Python control flow inside jit):

- The bit-blaster's clause pool is SHARED by all lanes (every
  path-feasibility query activates a subset via assumption literals),
  so the pool uploads once per version as a dense ``[C, K]`` int32
  matrix in HBM; per-lane state is only the assignment vector
  ``[B, V+1]`` in {-1 (false), 0 (unknown), +1 (true)}.

- One jitted solve = a full batched **DPLL search** (``lax.while_loop``
  around a vectorized clause scan): unit propagation by scatter-max of
  forced literals, dynamic DLIS decisions, per-lane trail levels and
  decision stacks, chronological backtracking on conflict.  UNSAT
  verdicts are sound both from a zero-decision BCP conflict and from an
  exhausted search (a clause *subset* being unsatisfiable under the
  lane's assumptions makes the full pool unsatisfiable under them).
  Completed assignments are verified on the host against the original
  term constraints before being trusted as SAT — so clauses wider than
  K may be dropped from the device pool without soundness loss.

- Lanes that exhaust the step or decision budget fall through to the
  native CDCL (the authoritative tail); lanes the device refutes leave
  their assumption nogood in the pool (cross-dispatch learning).

Sharding: the lane axis is data-parallel; ``parallel.mesh`` shards
``[B, V+1]`` across devices while the clause pool is replicated
(broadcast once over ICI) — see parallel/mesh.py.
"""

import contextlib
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from mythril_tpu.observability import spans as obs

log = logging.getLogger(__name__)

MAX_CLAUSE_WIDTH = 8  # wider clauses stay CPU-only (soundness preserved)
GATHER_STEPS = 768     # DPLL sweep budget (one clause scan per step)
GATHER_DECISIONS = 256  # decision-stack depth before bailing to CDCL
# Round-ladder budgets for the gather/cone tiers (see pallas_prop's
# ROUND_BUDGETS for the rationale): a FIXED geometric set so per-round
# shapes reuse the existing bucket grid; the last entry repeats until
# GATHER_STEPS is covered.  Watchdog EWMA keys carry the round budget
# ("gather:64" vs "gather:512") — a re-packed 64-step round must not
# inherit the deadline model of a 512-step one and trip false alarms.
GATHER_ROUND_BUDGETS = (64, 256, 512)
MAX_GATHER_CLAUSES = 8192  # beyond this the full-pool gather probe loses
MAX_GATHER_VARS = 8192     # to the CDCL tail outright (see check_assumption_sets)
# Union-cone gather tier (VERDICT r4 #4/#7): when the POOL outgrows the
# caps above but the batch's union defining cone still fits these, the
# dispatch ships only the cone (subset CSR, vars compacted to dense
# ids).  Measured cone histograms (BENCH_r05.json): scale-
# scenario frontiers stay ~10k clauses while their pools pass 40k, so
# this is the tier that keeps wide frontiers on the device as the pool
# deepens; -t3 cones measure 0.5M-2M clauses and stay host-bound by
# design.
MAX_CONE_GATHER_CLAUSES = 16384
MAX_CONE_GATHER_VARS = 8192
MAX_LEARNT_EXEMPTION = 8192  # absorbed-learnt budget exemption cap
FUTILE_DISPATCH_FUSE = 3   # consecutive zero-decision dispatches before
                           # the device is skipped for the context
SLOW_DISPATCH_FUSE_S = 10.0  # a single zero-decision dispatch slower than
                             # this trips the fuse immediately
FUSE_RETRY_PERIOD = 8   # fused contexts re-probe the device every N
MAX_FUSE_RETRIES = 3    # eligible rounds, at most this many times


def effective_min_lanes() -> int:
    """Structural lane floor for the batched frontier path, shared by
    laser/batch.py (entry gate) and the dispatch gate here so the two
    can never drift.  Lane-count ECONOMICS belong to the adaptive
    profit gate (projected CPU residue cost vs device_min_save_s): at
    the default knob setting the floor is relaxed to 4 so
    narrow-but-expensive frontiers (deep -t3 residues average ~200 ms
    of CDCL per query) reach that gate at all.  An operator who
    explicitly RAISES device_min_lanes above the default is asking to
    keep narrow frontiers off the device, and is honored verbatim."""
    from mythril_tpu.support.support_args import args

    knob = getattr(args, "device_min_lanes", 8)
    if knob > 8:
        return knob
    return max(2, min(knob, 4))


class DispatchStats:
    """Device-dispatch telemetry (read by bench.py ablations and the
    solver-statistics report so speedup claims stay attributable)."""

    def __init__(self):
        # construction must NOT cascade into the resilience/coalescer
        # resets below: this module imports lazily, and a run that never
        # dispatched (e.g. a resumed analysis whose journal already
        # covered every transaction) would otherwise wipe live
        # resilience counters (resumes, checkpoints_written) the moment
        # the checkpoint plane first touches dispatch_stats
        self._reset_own()

    def reset(self):
        self._reset_own()
        # degradation counters (watchdog_trips, dispatch_retries,
        # demotions, rpc_retries, faults_fired) live in the resilience
        # package and reset with this object so per-contract rows stay
        # per-contract
        from mythril_tpu.resilience.telemetry import resilience_stats

        resilience_stats.reset()
        # the admission queue is generation-scoped; clearing it with the
        # stats keeps per-contract bench rows from inheriting a stale
        # window (lazy import — coalesce reads these stats back)
        from mythril_tpu.ops.coalesce import reset_coalescer

        reset_coalescer()
        # the cone memo is (generation, pool_version)-scoped and would
        # invalidate itself, but clearing it with the stats keeps a
        # per-contract row's cone_memo_hits from counting against a
        # predecessor's still-cached entries
        from mythril_tpu.ops.incremental import reset_cone_memo

        reset_cone_memo()
        # fleet counters (parallel/fleet.py) are per-contract in bench
        # rows / meta.resilience, same as the resilience counters
        from mythril_tpu.parallel.fleet import fleet_stats

        fleet_stats.reset()

    def _reset_own(self):
        self.dispatches = 0        # device solve invocations
        self.lanes = 0             # total lanes sent to device
        self.unsat = 0             # lanes decided UNSAT on device
        self.sat_verified = 0      # lanes whose device model verified on host
        self.undecided = 0         # lanes handed to the CDCL tail
        self.host_probe_sat = 0    # lanes decided by host word-level probing
        self.mesh_dispatches = 0   # invocations through the sharded mesh path
        self.mesh_pool_rows = 0    # clause rows in the last mesh dispatch
        self.mesh_absorbed = 0     # absorbed CDCL learnts in that pool
        # dispatch attempts that bailed on the size caps (cone too large
        # for the dense kernel AND pool too large for the gather probe):
        # explains a zero dispatch count on small-contract corpora
        self.size_bailouts = 0
        # union-cone tier declines (cone itself past MAX_CONE_GATHER_*,
        # or an unwalked-var remap decline): names the fate of launch
        # attempts on deep pools, where -t3 cones measure 0.5M-2M
        # clauses (BENCH_r05.json) — a zero async_launches
        # count on a -t3 row is this counter, not a dead channel
        self.cone_bailouts = 0
        # True when the adaptive fuse disabled device dispatch for a
        # context after FUTILE_DISPATCH_FUSE zero-decision dispatches
        self.fused = False
        # dispatch attempts skipped because auto mode found only a CPU
        # jax backend (telemetry: explains zero dispatches on dev hosts)
        self.cpu_auto_skips = 0
        # total DPLL sweeps the dense kernel ran (wall-clock breakdown:
        # device solve time ≈ sweeps x per-sweep cost for the shape)
        self.device_sweeps = 0
        # straggler-aware sweep scheduling (round ladder; this PR):
        # lane_sweeps_total = sweeps x lane-bucket width (the MXU work
        # actually burned); lane_sweeps_active = per-lane live sweeps
        # (work that could still decide something).  Their ratio is the
        # headline sweep-utilization number — 1.0 means no lane ever
        # idled through a sibling's search.
        self.lane_sweeps_active = 0
        self.lane_sweeps_total = 0
        self.rounds = 0            # budgeted solve rounds executed
        self.repacks = 0           # survivor re-packs into smaller buckets
        # cross-dispatch lane coalescing (ops/coalesce.py): dispatches
        # that carried merged lanes from the admission queue, and lanes
        # deferred into the queue (their round fell back to the CDCL
        # tail; the merged dispatch pays them back via memos/nogoods)
        self.coalesced_dispatches = 0
        self.coalesced_lanes = 0
        self.coalesce_deferred = 0
        # lane-bucket utilization (satellite: bucket stats): real lanes
        # vs bucket slots across dispatches — shows the coalescer's
        # fill effect in bench rows independent of sweep counts
        self.lane_slots_filled = 0
        self.lane_slots_total = 0
        # wall-clock spent inside device dispatches (cone + build +
        # solve + fetch), for the bench breakdown
        self.device_s = 0.0
        # device id the active corpus shard last placed arrays on
        # (ops/device_placement.py; stays 0 on single-device hosts)
        self.corpus_shard_device = 0
        # dispatches skipped because the projected CPU cost of the
        # residue did not clear args.device_min_save_s
        self.profit_skips = 0
        # device paths skipped because the process was demoted (a
        # dispatch's own error said the device is gone) — explains
        # zero dispatches after a mid-run device loss
        self.unhealthy_skips = 0
        # transaction seeds replaced by dispatcher pre-split states
        # (laser/ethereum/lockstep_dispatch.py)
        self.presplit_states = 0
        # incremental dispatch plane (ops/incremental.py; this PR):
        # host->device payload bytes actually shipped (clause pools,
        # incidence coordinates, cone rows, assumption columns), full
        # pool (re)uploads vs delta appends into the resident pool,
        # lanes whose decision phases were seeded from a parent model,
        # and host-side cone/remap builds skipped via the cone memo
        self.h2d_bytes = 0
        self.pool_uploads = 0
        self.delta_uploads = 0
        self.warm_start_hits = 0
        self.cone_memo_hits = 0
        # persistent knowledge plane (persist/plane.py): analyses that
        # warm-started from a stored channel snapshot vs ones the store
        # had never seen — per-contract mirrors of the plane's process-
        # lifetime counters, so bench rows can attribute a cheap row to
        # persisted knowledge
        self.persist_warm_hits = 0
        self.persist_warm_misses = 0
        # word-level reasoning tier (smt/word_tier.py; this PR): lanes
        # decided UNSAT by empty abstractions / SAT by constant fold
        # without ever building CNF, total variable bits pinned by the
        # tier's known-bits propagation (each becomes a unit assumption
        # literal for the blaster), and wall-clock spent in the
        # abstract-propagation kernels (the `word.prop` span's sink)
        self.word_decided_unsat = 0
        self.word_decided_sat = 0
        self.word_tightened_bits = 0
        self.word_prop_s = 0.0
        # device-native propagation (ops/frontier.py; this PR):
        # adjacency-gather BCP iterations that replaced full-pool
        # sweeps (device_sweeps keeps counting FULL sweeps, so the
        # sweeps-per-lane headline stays comparable across rounds),
        # and first-UIP clauses learned in-kernel and accepted into
        # the pool's nogood channel (they reach the resident pool as
        # delta uploads on the next dispatch)
        self.frontier_steps = 0
        self.learned_clauses = 0
        # resident solver (ops/resident.py; this PR): every REAL device
        # kernel invocation — ladder rounds, bisection sub-dispatches,
        # resident solves, mesh solves, dense pallas rounds, one-shot
        # prefetch solves — counts here; bench divides by analyses for
        # the dispatches_per_analysis headline (the host round-trip
        # cost the resident kernel exists to kill)
        self.device_dispatch_calls = 0
        # frontiers the adaptive fuse kept off the device (a periodic
        # retry dispatches, so it is not one): unlike the backend's
        # fused_skips, which restarts per solver context and at each
        # re-arm, this count restarts only with dispatch_stats
        self.fuse_skips = 0
        # resident-kernel dispatches and their exit taxonomy (device-
        # decided: all lanes retired / iteration budget exhausted /
        # device-side stall watchdog tripped) — profile_t3 reports the
        # split, and a nonzero watchdog count is the chaos signal
        self.resident_dispatches = 0
        self.resident_exit_all_decided = 0
        self.resident_exit_budget = 0
        self.resident_exit_watchdog = 0
        # in-kernel loop iterations over those dispatches (the ``itc``
        # each returns): the search's length, so device seconds over it
        # is the cost of one iteration
        self.resident_iterations = 0
        # dense dispatches the Pallas tier declined in favor of the
        # resident kernel (satellite: both ladders share one state
        # layout) — explains a dense-tier quiet round under the
        # resident default
        self.resident_delegations = 0
        # symbolic lockstep tier (laser/ethereum/symbolic_lockstep.py):
        # interpreter (state, opcode) steps executed inside batched
        # segments, and the wall-clock of those segments (the
        # `svm.segment` span's sink) — their ratio is the states_per_s
        # headline
        self.states_stepped = 0
        self.segment_s = 0.0
        # limb-plane carriage inside those segments: known bits over
        # total bits across every shadowed stack push — the density
        # number that says how much of the symbolic traffic the
        # word_prop transfers could pin
        self.plane_known_bits = 0
        self.plane_total_bits = 0
        # NEEDS_HOST tail: lanes handed back to the serial interpreter
        # at a segment boundary, keyed by the opcode that parked them
        # ("cap" = op budget, "end-of-code" = fell off the bytecode) —
        # bench divides boundaries by states_stepped for the
        # host_boundaries_per_1k_states headline, profile_t3 prints
        # the cause split
        self.needs_host_boundaries = 0
        self.boundary_causes = {}
        # memory/storage/keccak data planes (symbolic_lockstep): lane-
        # ops executed in-segment through each plane, and SHA3 results
        # hashed on-device by ops/keccak.py instead of parking
        self.mem_plane_ops = 0
        self.storage_plane_ops = 0
        self.keccak_device_hashes = 0
        # veritesting tier (laser/ethereum/veritest.py): re-converged
        # sibling pairs collapsed to one lane (merges / merged_lanes),
        # If terms those joins minted (merge_ites — the budget
        # MYTHRIL_TPU_MERGE_MAX_ITES bounds per join), joins declined
        # or degraded to plain forking (merge_aborts), and the
        # frontier-subsumption sweeps with the lanes they retired
        # without ever reaching a solver; merge_span_s is the
        # svm.merge/svm.subsume span sink
        self.merges = 0
        self.merged_lanes = 0
        self.merge_ites = 0
        self.merge_aborts = 0
        self.subsume_sweeps = 0
        self.subsumed_lanes = 0
        self.merge_span_s = 0.0

    def as_dict(self):
        from mythril_tpu.parallel.fleet import fleet_stats
        from mythril_tpu.resilience.telemetry import resilience_stats

        d = dict(self.__dict__)
        d.update(resilience_stats.as_dict())
        d.update({
            f"fleet_{key}": value
            for key, value in fleet_stats.as_dict().items()
        })
        return d


dispatch_stats = DispatchStats()


def _require_jax():
    import jax
    import jax.numpy as jnp

    from mythril_tpu.ops import configure_jax

    configure_jax()
    return jax, jnp


class DevicePool:
    """Device-resident dense clause matrix, refreshed on pool growth."""

    def __init__(self):
        self.version = -1
        self.lits = None        # [C, K] int32 (signed, 0 = pad)
        self.num_vars = 0
        self.num_clauses = 0
        self.dropped = 0
        self.consumed = 0       # ctx.clauses_py rows reflected on device
        self.filled = 0         # non-pad rows used in the bucket
        # literal→clause-row adjacency for the frontier tier
        # (ops/frontier.py), host + device copies; invalidated with
        # the rows they index (refresh and delta appends)
        self._adj_np = None
        self._adj_dev = None

    @staticmethod
    def _bucket(n: int) -> int:
        """Round up to a power of two so device shapes stay stable while
        the pool grows (avoids re-jitting every refresh)."""
        size = 256
        while size < n:
            size *= 2
        return size

    @staticmethod
    def _safe_to_donate() -> bool:
        """True when no async prefetch worker could be holding the
        stale pool array (ops/async_dispatch.py runs one at a time)."""
        from mythril_tpu.ops import async_dispatch

        dispatcher = async_dispatch._dispatcher
        if dispatcher is None:
            return True
        thread = dispatcher._live_thread
        return dispatcher.pending is None and (
            thread is None or not thread.is_alive()
        )

    def refresh(self, ctx, num_vars: int):
        """Full rebuild from the native pool's CSR store (one bulk
        padded-row fetch — no Python tuple traffic)."""
        _, jnp = _require_jax()
        total = ctx.pool.num_clauses
        rows, dropped = ctx.pool.padded_rows(0, total, MAX_CLAUSE_WIDTH)
        real_rows = max(1, len(rows))  # keep one inert all-zero row
        # pad clause count to the bucket with inert all-zero rows
        target_c = self._bucket(real_rows)
        mat = np.zeros((target_c, MAX_CLAUSE_WIDTH), dtype=np.int32)
        if len(rows):
            mat[: len(rows)] = rows
        self.lits_np = mat  # host mirror
        # (the mesh path shards from here without a device round-trip)
        stale = self.lits
        with obs.span("upload.pool", cat="h2d", bytes=int(mat.nbytes),
                      rows=target_c), obs.wait("upload.pool"):
            self.lits = jnp.asarray(self.lits_np)
        if stale is not None and self._safe_to_donate():
            # donate the stale device buffer eagerly: a refresh doubles
            # the pool bucket, and holding both generations until GC
            # runs is exactly the HBM spike that evicts sibling arrays.
            # Skipped while an async prefetch is in flight — its worker
            # may have captured this very array, and deleting it under
            # the kernel would fail the (opportunistic) batch for no
            # HBM win worth having.
            try:
                stale.delete()
            except Exception:  # noqa: BLE001 — donation is best-effort
                pass
        dispatch_stats.pool_uploads += 1
        dispatch_stats.h2d_bytes += int(mat.nbytes)
        obs.counter("pool.rows", resident=real_rows, bucket=target_c)
        self._adj_np = None
        self._adj_dev = None
        self.num_vars = self._bucket(num_vars)
        self.num_clauses = target_c
        self.dropped = dropped
        self.consumed = total
        self.filled = real_rows
        # vars with no occurrence in any retained row (bucket padding,
        # vars whose defining clauses were too wide): callers preassign
        # them so the DPLL never spends decisions completing them
        self.used = np.zeros(self.num_vars + 1, dtype=bool)
        occurring = np.abs(self.lits_np[:real_rows]).ravel()
        self.used[occurring[occurring <= self.num_vars]] = True

    def append(self, ctx, num_vars: int) -> bool:
        """Reflect the pool delta since ``consumed`` in-place when it
        fits the existing buckets: pad rows are overwritten on host and
        device (a device .at[].set touches only the delta) — no full
        rebuild/upload per dispatch while the CDCL tail keeps learning
        clauses."""
        if self.lits is None or self._bucket(num_vars) > self.num_vars:
            return False
        total = ctx.pool.num_clauses
        rows, dropped = ctx.pool.padded_rows(
            self.consumed, total, MAX_CLAUSE_WIDTH
        )
        if self.filled + len(rows) > self.num_clauses:
            return False
        self.dropped += dropped
        if len(rows):
            self.lits_np[self.filled : self.filled + len(rows)] = rows
            with obs.span("upload.delta", cat="h2d",
                          bytes=int(rows.nbytes), rows=len(rows)), \
                    obs.wait("upload.delta"):
                self.lits = self.lits.at[
                    self.filled : self.filled + len(rows)
                ].set(rows)
            self.filled += len(rows)
            occurring = np.abs(rows).ravel()
            self.used[occurring[occurring <= self.num_vars]] = True
            # the dispatch ships only the appended rows, not the pool
            dispatch_stats.delta_uploads += 1
            dispatch_stats.h2d_bytes += int(rows.nbytes)
            obs.counter("pool.rows", resident=self.filled,
                        bucket=self.num_clauses)
            # appended rows (CDCL learnts, device-learned nogoods)
            # need adjacency entries too — rebuilt lazily on the next
            # frontier dispatch
            self._adj_np = None
            self._adj_dev = None
        self.consumed = total
        return True

    def adjacency_dev(self):
        """Device copy of the literal→clause-row adjacency over the
        resident rows (ops/frontier.py), built and uploaded at most
        once per pool refresh/append."""
        if self._adj_dev is not None:
            return self._adj_dev
        from mythril_tpu.ops.frontier import build_adjacency

        _, jnp = _require_jax()
        if self._adj_np is None:
            self._adj_np = build_adjacency(
                self.lits_np[: self.filled], self.num_vars + 1
            )
        with obs.span("upload.adjacency", cat="h2d",
                      bytes=int(self._adj_np.nbytes)), \
                obs.wait("upload.adjacency"):
            self._adj_dev = jnp.asarray(self._adj_np)
        dispatch_stats.h2d_bytes += int(self._adj_np.nbytes)
        return self._adj_dev


def build_round_lane(
    num_vars: int,
    budget: int,
    max_decisions: int = GATHER_DECISIONS,
    reduce_hook=None,
):
    """Resumable per-lane DPLL round (traceable; the round-ladder core
    of the gather tier).

    ``round_lane(lits[C,K], assign, lvl, dvar, dphase, dflip, depth,
    status, step, pref) -> same tuple`` advances the lane's search by
    at most ``budget`` sweeps from the given state.  ``pref[V1]`` int8
    is the warm-start decision-phase preference (0 = no preference,
    DLIS majority polarity as before): it rides the lane state so
    bucket re-packs carry it, is never written, and only biases which
    phase a decision tries FIRST — backtracking still explores the
    flip, so UNSAT/SAT semantics are untouched (ops/incremental.py).
    Status is RAW: 0 live,
    1 complete assignment for the device clause subset (host verifies),
    2 sound UNSAT, 3 decision-stack bail (the ladder retires such lanes
    as undecided and never re-enters them).  ``step`` must be zeroed by
    the caller per round; on return it holds the lane's OWN active
    sweep count for the round (under vmap the loop runs to the slowest
    live lane, but each lane's carry freezes once its cond fails), so
    the driver reads total iterations as max(step) and per-lane active
    work as sum(step) — the sweep-utilization split.
    """
    jax, jnp = _require_jax()

    V1 = num_vars + 1
    D = max(1, min(max_decisions, V1))

    def clause_scan(lits, assign_lane):
        # lit value: +1 sat, -1 false, 0 unknown; padding counts false
        var_idx = jnp.abs(lits)                       # [C, K]
        vals = jnp.sign(lits) * assign_lane[var_idx]  # [C, K]
        is_real = lits != 0
        real_row = jnp.any(is_real, axis=1)
        sat = jnp.any((vals > 0) & is_real, axis=1)           # [C]
        num_unknown = jnp.sum((vals == 0) & is_real, axis=1)  # [C]
        all_false = jnp.all((vals < 0) | ~is_real, axis=1) & real_row
        conflict = jnp.any(all_false)
        unsat_yet = (~sat) & real_row
        # unit clauses: exactly one unknown literal and not satisfied
        unit = unsat_yet & (num_unknown == 1)
        open_c = unsat_yet & (num_unknown > 1)
        unknown_here = (vals == 0) & is_real
        # the single unknown literal of each unit clause
        forced_lit = jnp.sum(
            jnp.where(unit[:, None] & unknown_here, lits, 0), axis=1
        )  # [C]
        forced_pos = jnp.zeros(V1, dtype=jnp.int32).at[
            jnp.where(forced_lit > 0, forced_lit, 0)
        ].max(jnp.where(forced_lit > 0, 1, 0))
        forced_neg = jnp.zeros(V1, dtype=jnp.int32).at[
            jnp.where(forced_lit < 0, -forced_lit, 0)
        ].max(jnp.where(forced_lit < 0, 1, 0))
        # decision scores: unknown occurrences in open clauses, split by
        # polarity (scatter-add over the literal matrix)
        open_unknown = unknown_here & open_c[:, None]
        spos = jnp.zeros(V1, dtype=jnp.int32).at[var_idx].add(
            (open_unknown & (lits > 0)).astype(jnp.int32)
        )
        sneg = jnp.zeros(V1, dtype=jnp.int32).at[var_idx].add(
            (open_unknown & (lits < 0)).astype(jnp.int32)
        )
        return forced_pos, forced_neg, conflict, spos, sneg

    def round_lane(lits, assign, lvl0, dvar0, dphase0, dflip0, depth0,
                   status0, step0, pref0):
        idx = jnp.arange(V1)
        didx = jnp.arange(D)  # slot l holds decision level l+1

        def body(carry):
            assign, lvl, dvar, dphase, dflip, depth, status, step = carry
            pos, neg, conflict, spos, sneg = clause_scan(lits, assign)
            if reduce_hook is not None:
                pos, neg, conflict, spos, sneg = reduce_hook(
                    pos, neg, conflict, spos, sneg
                )
            free = (assign == 0) & (idx > 1)  # col 1 = TRUE anchor
            force_pos = (pos > 0) & free
            force_neg = (neg > 0) & free
            conflict = conflict | jnp.any(force_pos & force_neg)
            has_force = jnp.any(force_pos | force_neg)
            open_any = jnp.any(free)
            active = status == 0

            # conflict: backtrack to the deepest unflipped decision
            unflipped = (didx < depth) & (~dflip)
            Lm = jnp.max(jnp.where(unflipped, didx + 1, 0))  # 0 = none
            unsat_now = active & conflict & (Lm == 0)
            do_bt = active & conflict & (Lm > 0)
            bslot = jnp.maximum(Lm - 1, 0)
            bvar = dvar[bslot]
            bphase = -dphase[bslot]
            A1 = jnp.where(
                do_bt & (assign != 0) & (lvl >= Lm), 0, assign
            ).astype(jnp.int8)
            A1 = jnp.where(do_bt & (idx == bvar), bphase, A1).astype(
                jnp.int8
            )
            lvl1 = jnp.where(do_bt & (idx == bvar), Lm, lvl)
            popped = do_bt & (didx >= Lm)
            at_b = do_bt & (didx == bslot)
            dvar1 = jnp.where(popped, 0, dvar)
            dphase1 = jnp.where(
                popped, 0, jnp.where(at_b, bphase, dphase)
            ).astype(jnp.int8)
            dflip1 = jnp.where(popped, False, jnp.where(at_b, True, dflip))
            depth1 = jnp.where(do_bt, Lm, depth)

            # quiet + forced: assign all forced literals at this level
            do_force = active & (~conflict) & has_force
            assigned_now = do_force & (force_pos | force_neg)
            delta = jnp.where(force_pos, 1, -1).astype(jnp.int8)
            A2 = jnp.where(assigned_now, delta, A1).astype(jnp.int8)
            lvl2 = jnp.where(assigned_now, depth, lvl1)

            # quiet + open: decide (dynamic DLIS var + polarity)
            want = active & (~conflict) & (~has_force) & open_any
            can = depth < D
            do_dec = want & can
            bail = want & (~can)
            score = jnp.where(free, spos + sneg + 1, -1)
            var = jnp.argmax(score)
            dlis = jnp.where(spos[var] >= sneg[var], 1, -1).astype(
                jnp.int8
            )
            # warm start: a parent model's phase wins over DLIS where
            # one exists (search-order bias only; the flip is still
            # explored on backtrack)
            phase = jnp.where(pref0[var] != 0, pref0[var], dlis).astype(
                jnp.int8
            )
            ndepth = depth + 1
            # don't-care cascade: free vars in no open clause have every
            # containing clause satisfied (no units exist in the decide
            # branch), so any phase is safe — assign them in bulk at the
            # new level (they pop with it on backtrack)
            dontcare = free & (spos + sneg == 0)
            newly = do_dec & (dontcare | (idx == var))
            A3 = jnp.where(
                newly,
                jnp.where(idx == var, phase, jnp.int8(1)),
                A2,
            ).astype(jnp.int8)
            lvl3 = jnp.where(newly, ndepth, lvl2)
            at_new = do_dec & (didx == depth)
            dvar2 = jnp.where(at_new, var, dvar1)
            dphase2 = jnp.where(at_new, phase, dphase1).astype(jnp.int8)
            dflip2 = jnp.where(at_new, False, dflip1)
            depth2 = jnp.where(do_dec, ndepth, depth1)

            # quiet + complete: SAT candidate
            done_sat = active & (~conflict) & (~has_force) & (~open_any)
            status1 = jnp.where(unsat_now, 2, status)
            status1 = jnp.where(done_sat, 1, status1)
            status1 = jnp.where(bail, 3, status1)  # 3 = budget-bailed
            return (A3, lvl3, dvar2, dphase2, dflip2, depth2, status1,
                    step + 1)

        def cond(carry):
            return (carry[6] == 0) & (carry[7] < budget)

        init = (assign, lvl0, dvar0, dphase0, dflip0, depth0, status0,
                step0)
        out = jax.lax.while_loop(cond, body, init)
        return out + (pref0,)  # pref rides the state tuple, unchanged

    return round_lane


def build_solve_lane(
    num_vars: int,
    reduce_hook=None,
    max_steps: int = GATHER_STEPS,
    max_decisions: int = GATHER_DECISIONS,
):
    """Build the per-lane gather-style DPLL solve function (traceable).

    ``solve_lane(lits[C,K], assign[V+1]) -> (assign', status)``
    with status 0 = undecided (budget exhausted), 1 = complete
    satisfying assignment for the device clause subset (the host must
    verify it against the original terms — wide clauses are dropped
    from the gather pool), 2 = sound UNSAT (BCP conflict at zero
    decisions, or a DPLL search that exhausted both phases of every
    decision — sound under clause subsets, since a subset being
    unsatisfiable under the lane's assumptions makes the full pool
    unsatisfiable under them).

    The search is chronological DPLL: trail levels per variable, an
    explicit decision stack, dynamic DLIS decisions (the free variable
    with the most open-clause occurrences, majority polarity), and
    backtracking to the deepest unflipped decision on conflict.  One
    step = one clause scan; the search core is the resumable
    :func:`build_round_lane` run as a single full-budget round.

    This single definition backs the one-shot jit path
    (``make_solve_step``, which the async prefetch's sliced rounds
    reproduce bit for bit) and the mesh-sharded path
    (parallel/mesh.py), which passes a
    ``reduce_hook(pos, neg, conflict, spos, sneg)`` merging
    forced-literal votes, conflict flags and decision scores across
    clause shards (psum over the ``cp`` mesh axis); the merged
    quantities are identical on every clause shard, so all replicas of
    a lane take the same decisions and stay in lockstep.
    """
    _, jnp = _require_jax()

    V1 = num_vars + 1
    D = max(1, min(max_decisions, V1))
    rnd = build_round_lane(num_vars, max_steps, max_decisions,
                           reduce_hook)

    def solve_lane(lits, assign_lane):
        out = rnd(
            lits, assign_lane,
            jnp.zeros(V1, dtype=jnp.int32),
            jnp.zeros(D, dtype=jnp.int32),
            jnp.zeros(D, dtype=jnp.int8),
            jnp.zeros(D, dtype=bool),
            jnp.int32(0),
            jnp.int32(0),
            jnp.int32(0),
            jnp.zeros(V1, dtype=jnp.int8),  # no warm-start preference
        )
        assign, status = out[0], out[6]
        status = jnp.where(status == 3, 0, status)  # bailed = undecided
        return assign, status

    return solve_lane


def make_solve_step(num_vars: int):
    """Jitted single-chip lockstep solve over the whole lane batch:
    fn(lits[C,K], assign[B,V+1]) -> (assign', status[B])."""
    jax, _ = _require_jax()

    batched = jax.vmap(build_solve_lane(num_vars), in_axes=(None, 0))
    return jax.jit(batched)


def make_round_step(num_vars: int, budget: int):
    """Jitted batched round for the gather ladder:
    fn(lits[C,K], *state[B, ...]) -> state' (see build_round_lane)."""
    jax, _ = _require_jax()

    batched = jax.vmap(
        build_round_lane(num_vars, budget),
        in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    )
    return jax.jit(batched)


def warm_pref_row(ctx, width: int, cone_vars=None, offset: int = 1,
                  lanes: int = 0, dtype=np.int8):
    """Warm-start decision-phase row for one dispatch, or None.

    Pulls the newest tagged SAT model from the blast context's
    recent-models channel (BlastContext.warm_phase_vector — phase
    saving across the fork tree: sibling/ancestor states share long
    constraint prefixes, so the parent's satisfying phases are the
    best first guess for the child's search).  ``cone_vars`` remaps
    the var-indexed phases onto compact cone columns
    (``cone_vars[i] -> column i + offset``: the gather cone tier packs
    at offset 1, the Pallas union layout at offset 2); None means the
    identity layout of the full-pool tier.  Counts ``lanes`` into
    ``warm_start_hits`` when a usable row exists.  Honors the
    ``MYTHRIL_TPU_WARM_START`` kill switch."""
    from mythril_tpu.ops.incremental import warm_start_enabled

    if not warm_start_enabled():
        return None
    warm = ctx.warm_phase_vector(ctx.solver.num_vars)
    if warm is None:
        return None
    row = np.zeros(width, dtype)
    if cone_vars is None:
        n = min(width, len(warm))
        row[:n] = warm[:n]
    else:
        cv = np.asarray(cone_vars, np.int64)
        vals = np.zeros(len(cv), np.int8)
        valid = cv < len(warm)
        vals[valid] = warm[cv[valid]]
        limit = max(0, min(len(cv), width - offset))
        row[offset:offset + limit] = vals[:limit]
    if not np.any(row):
        return None
    dispatch_stats.warm_start_hits += lanes
    return row


def lane_bucket(n: int, floor: int = 4) -> int:
    """Power-of-two lane-bucket width (shared with the coalescer, whose
    fill targets must match the shapes the ladder actually runs)."""
    size = floor
    while size < n:
        size *= 2
    return size


class BatchedSatBackend:
    """Host-side orchestration of the device lockstep solver."""

    def __init__(self):
        import threading

        self.pool = DevicePool()
        self.pool_generation = -1  # BlastContext.generation of the pool
        self._step_cache: Dict[int, object] = {}
        # the async prefetch worker compiles steps off-thread; the lock
        # keeps host + worker from double-compiling or evicting each
        # other's entries
        self._step_lock = threading.Lock()
        # adaptive fuse: consecutive engaged dispatches that decided
        # zero lanes; past the threshold the device is skipped for the
        # rest of this blast context (paying kernel-dispatch latency
        # for nothing but CDCL-tail work is strictly worse than going
        # to the tail directly)
        self.futile_dispatches = 0
        self.futile_ctx_generation = -1
        self.fused_generation = -1
        self.fused_skips = 0   # rounds skipped since the fuse blew
        self.fuse_retries = 0  # periodic retry dispatches spent
        self.fuse_was_slow = False  # fuse tripped by one slow dispatch
        # True iff the last check_assumption_sets actually ran a device
        # (or interpret-mode kernel) pass — telemetry keys off this so
        # bail-outs don't inflate the attribution counters
        self.device_engaged = False

    def check_assumption_sets(
        self, ctx, assumption_sets: List[List[int]], search: bool = True
    ) -> List[Optional[bool]]:
        """For each assumption set over ctx's clause pool return
        True (verified SAT candidate assignment), False (sound UNSAT), or
        None (undecided — caller falls back to CDCL).

        The returned SAT verdicts are *candidates*: the caller must
        verify the model against the original constraints (we only
        guarantee consistency with the device-resident clause subset).
        ``search=False`` keeps dispatches BCP-only (see
        PallasSatBackend.check_assumption_sets).
        """
        from mythril_tpu.ops.pallas_prop import get_pallas_backend
        from mythril_tpu.resilience.watchdog import (
            DispatchAbandoned, get_watchdog,
        )

        self.device_engaged = False
        pallas = get_pallas_backend()
        if pallas.available_for(ctx):
            # fused MXU kernels over the per-call cone: dense incidence
            # matmuls, batched DPLL, no clause-width cap.  None means
            # the cone exceeded the dense caps — gather path below.
            # Supervised: the dense path's chunk loops checkpoint
            # raise_if_cancelled() before each ctx touch, so an
            # abandoned worker can't race the host on the native pool.
            try:
                dense = get_watchdog().supervised(
                    "pallas",
                    lambda: pallas.check_assumption_sets(
                        ctx, assumption_sets, search=search
                    ),
                )
            except DispatchAbandoned as exc:
                return self._abandon(ctx, exc, len(assumption_sets))
            if dense is not None:
                results, assignments = dense
                self.last_assignments = assignments
                self.device_engaged = True
                return results

        verdict, num_vars = self._gather_eligibility(ctx)
        if verdict is not None:
            if verdict == "size_bailouts":
                # the POOL is too big, but the batch's union cone may
                # still fit the cone tier — ship just the cone
                cone_result = self.check_cone_gather(ctx, assumption_sets)
                if cone_result is not None:
                    return cone_result
                dispatch_stats.cone_bailouts += 1
            # telemetry names the cause (a zero dispatch count must be
            # attributable from the artifact alone)
            if verdict == "unhealthy_skips":
                from mythril_tpu.ops.device_health import count_skip

                count_skip("gather dispatch")
            else:
                setattr(dispatch_stats, verdict,
                        getattr(dispatch_stats, verdict) + 1)
            self.last_assignments = np.zeros(
                (len(assumption_sets), num_vars + 1), np.int8
            )
            return [None] * len(assumption_sets)

        assign = self._sync_pool_and_assign(ctx, assumption_sets, num_vars)
        jax, jnp = _require_jax()
        batch = len(assumption_sets)

        self.device_engaged = True
        from mythril_tpu.resilience import faults

        if len(jax.devices()) > 1:
            # multi-chip: lanes ride the dp axis, the clause pool is
            # sharded over cp with psum-merged BCP (parallel/mesh.py);
            # lits come from the pool's host mirror (no device->host
            # round trip for an unchanged pool)
            from mythril_tpu.parallel.mesh import (
                get_mesh, sharded_frontier_solve,
            )

            pool_lits_np = self.pool.lits_np
            # the sharded layout re-broadcasts the pool mirror per
            # dispatch; the resident-pool savings are single-chip only
            dispatch_stats.h2d_bytes += int(pool_lits_np.nbytes)
            # the mesh solve is monolithic (no per-round retirement),
            # so the lane counter samples once per dispatch
            obs.counter("lanes.live", live=batch)

            def _solve_mesh():
                faults.maybe_fault_dispatch()
                dispatch_stats.device_dispatch_calls += 1
                fa, st = sharded_frontier_solve(
                    get_mesh(), pool_lits_np, assign,
                )
                # np.asarray blocks until the kernel finished — this is
                # exactly where a hung dispatch parks, so it belongs
                # inside the supervised region
                return np.asarray(st), np.asarray(fa)

            try:
                status, final_assign = get_watchdog().supervised(
                    "mesh", _solve_mesh
                )
            except DispatchAbandoned as exc:
                return self._abandon(ctx, exc, batch)
            dispatch_stats.mesh_dispatches += 1
            # rows scanned per shard ride cp; absorbed CDCL learnts are
            # inside pool.filled (refresh folds them in above), so this
            # pair documents that the learned-clause channel reaches the
            # sharded path
            dispatch_stats.mesh_pool_rows = self.pool.filled
            dispatch_stats.mesh_absorbed = getattr(
                ctx, "absorbed_learnt_count", 0
            )
        else:
            # round-laddered lockstep solve: budgeted rounds, lane
            # retirement, bucket re-packing (supervision + fault
            # injection happen per round inside the ladder).  With the
            # frontier tier on, rounds gather only clause rows adjacent
            # to recently-assigned literals (the resident pool's
            # adjacency index) and learn first-UIP clauses on device;
            # column space here is the pool's own variable space, so
            # learned literals harvest with no remap
            from mythril_tpu.ops.frontier import frontier_enabled

            frontier = None
            if frontier_enabled():
                frontier = {"adj": self.pool.adjacency_dev(),
                            "ctx": ctx, "col_to_var": None}
            try:
                status, final_assign = self._solve_gather_ladder(
                    "gather", self.pool.lits, assign,
                    pref=warm_pref_row(ctx, assign.shape[1],
                                       lanes=batch),
                    frontier=frontier,
                )
            except DispatchAbandoned as exc:
                return self._abandon(ctx, exc, batch)
        status, final_assign = faults.maybe_corrupt_lanes(
            status, final_assign
        )

        results: List[Optional[bool]] = []
        self.last_assignments = final_assign
        for lane in range(batch):
            if status[lane] == 2:
                results.append(False)
            else:
                results.append(None)  # candidate: host verifies the model
        return results

    def _abandon(self, ctx, exc, batch: int):
        """Terminal rung of the escalation ladder, context scope: the
        watchdog gave up on this dispatch (and already re-probed /
        process-demoted as warranted), so this analysis context goes to
        the native CDCL tail — the same machinery the adaptive fuse
        uses, with retries disabled (each fuse retry could wedge 10s+
        again).  Every in-flight lane returns undecided, so the caller
        re-solves it on the tail: no frontier state is dropped, findings
        match the fault-free run, only the speedup is lost."""
        self.device_engaged = False
        self.futile_ctx_generation = ctx.generation
        self.fused_generation = ctx.generation
        self.fuse_was_slow = True
        dispatch_stats.fused = True
        log.warning(
            "%s; context demoted to the native CDCL tail "
            "(results unchanged, device speedup lost)", exc,
        )
        self.last_assignments = np.zeros(
            (batch, ctx.solver.num_vars + 1), np.int8
        )
        return [None] * batch

    def _cached_round(self, bucket: int, budget: int):
        """Jitted ladder round for (pool bucket, step budget) — budgets
        come from the fixed GATHER_ROUND_BUDGETS set, so the key space
        stays a small grid and nothing recompiles after warmup.
        Thread-safe: shared by the sync ladder and the async prefetch
        worker."""
        return self._cached(("round", bucket, budget),
                            lambda: make_round_step(bucket, budget))

    def _prefetch_rounds(self, bucket: int, lits, assign, **extra):
        """The async prefetch's GATHER_STEPS-step solve, sliced into
        rounds of the sync ladder's first budget (the same compiled
        round), so a synchronous caller can stop it between two rounds.

        A generator: each ``next`` enqueues one round and yields
        ``{"status", "assign", "last", **extra}`` as device arrays,
        without waiting for the round.  ``step`` restarts at 0 every
        round, so each lane runs at most GATHER_STEPS steps in all: run
        to its last round, the lane states equal ``make_solve_step``'s,
        bit for bit (statuses raw: 3 is a decision-stack bail)."""
        _, jnp = _require_jax()
        budget = GATHER_ROUND_BUDGETS[0]
        rounds = GATHER_STEPS // budget
        step_fn = self._cached_round(bucket, budget)
        batch, V1 = assign.shape
        D = max(1, min(GATHER_DECISIONS, V1))
        zero = jnp.zeros(batch, jnp.int32)
        # (assign, lvl, dvar, dphase, dflip, depth, status, step, pref)
        state = (
            jnp.asarray(assign), jnp.zeros((batch, V1), jnp.int32),
            jnp.zeros((batch, D), jnp.int32), jnp.zeros((batch, D), jnp.int8),
            jnp.zeros((batch, D), bool), zero, zero, zero,
            jnp.zeros((batch, V1), jnp.int8),  # no warm-start preference
        )
        lits = jnp.asarray(lits)
        for i in range(rounds):
            state = step_fn(lits, *state[:7], zero, state[8])
            yield dict(extra, status=state[6], assign=state[0],
                       last=i == rounds - 1)

    def _cached_frontier_round(self, bucket: int, budget: int):
        """Jitted frontier round (ops/frontier.py) — the cache key
        carries the fan/period knobs so tests re-pinning the env never
        get a stale trace."""
        from mythril_tpu.ops.frontier import (
            frontier_fan, frontier_period, make_frontier_round_step,
        )

        key = ("frontier", bucket, budget, frontier_fan(),
               frontier_period())
        return self._cached(
            key,
            lambda: make_frontier_round_step(bucket, budget,
                                             GATHER_DECISIONS),
        )

    def _cached_resident(self, bucket: int):
        """Jitted resident solve (ops/resident.py) — every knob that
        bakes into the trace rides the cache key, so tests re-pinning
        budget/watchdog/extra env never get a stale compilation."""
        from mythril_tpu.ops import resident as RK
        from mythril_tpu.ops.frontier import frontier_fan, frontier_period

        key = ("resident", bucket, RK.resident_budget(),
               RK.resident_watchdog_limit(), RK.resident_extra_cap(),
               frontier_fan(), frontier_period())
        return self._cached(
            key,
            lambda: RK.make_resident_step(bucket, GATHER_DECISIONS),
        )

    def _harvest_round_learnts(self, state, live, frontier) -> None:
        """Pull the round's first-UIP clauses off the lane buffers and
        feed them to the blast context's nogood channel
        (ops/frontier.harvest_learned).  Accepted clauses reach the
        native CDCL immediately and the device-resident pool as an
        append-only delta upload on the next dispatch — the
        learned-clause lifecycle the resident-pool telemetry tracks
        (``learned_clauses`` / ``delta_uploads``)."""
        from mythril_tpu.ops.frontier import harvest_learned

        counts = state["nlearn"][: live.size]
        if not counts.any():
            return
        rows = []
        for lane in np.nonzero(counts)[0]:
            rows.extend(state["learned"][lane, : int(counts[lane])])
        accepted = harvest_learned(
            frontier["ctx"], rows, frontier.get("col_to_var")
        )
        dispatch_stats.learned_clauses += accepted

    def _cached(self, key, build):
        with self._holding_step_lock():
            step = self._step_cache.get(key)
            if step is not None:
                return step
        built = build()
        with self._holding_step_lock():
            step = self._step_cache.setdefault(key, built)
            if len(self._step_cache) > 12:
                for stale in list(self._step_cache):
                    if stale != key and len(self._step_cache) > 12:
                        del self._step_cache[stale]
        return step

    @contextlib.contextmanager
    def _holding_step_lock(self):
        """The step-cache lock, shared with the async prefetch worker:
        a thread that finds it held waits in a ``device.wait``."""
        if not self._step_lock.acquire(blocking=False):
            with obs.wait("step_lock"):
                self._step_lock.acquire()
        try:
            yield
        finally:
            self._step_lock.release()

    def _solve_resident(self, key_base: str, lits, assign, pref=None,
                        frontier=None):
        """Thin supervisor over the persistent resident kernel
        (ops/resident.py): ONE dispatch in, a verdict/trail/learned-
        clause bundle out.  The entire round ladder — frontier queues,
        DLIS decisions, first-UIP learning with mid-dispatch append of
        learned rows to the shared extra pool, mask-level lane
        retirement, and the device-side budget/stall-watchdog exit —
        runs inside the kernel; the host's job shrinks to seeding
        state, supervising the dispatch, and harvesting.

        What the multi-dispatch ladder guaranteed is preserved:

        - **EWMA watchdog**: the dispatch runs under ONE
          ``resident:{lane bucket}`` key family (satellite: no more
          key-per-round-budget proliferation) with the same deadline
          model; a cold key gets the full cap (jit compile dominates).
        - **retry -> bisect -> quarantine**: dispatch escalation goes
          through the SAME :meth:`_dispatch_round` rungs.  Only the
          per-lane fields ride bisection slicing; the shared extra
          pool / counters are re-seeded zero for every attempt (an
          empty learned pool is always a sound start), and the
          exit-reason telemetry is recorded per completed kernel
          invocation.
        - **drain seam**: honored at the dispatch boundary — a drain
          requested before launch returns every lane undecided so the
          analysis can land its final checkpoint; one in flight is
          bounded by the EWMA deadline.
        - **kill switch**: ``MYTHRIL_TPU_RESIDENT_KERNEL=0`` keeps the
          exact multi-dispatch ladders (see ``_solve_gather_ladder``).

        Returns (status[batch] int32 with bails mapped to undecided,
        final assign[batch, V1] int8) — the ladder's exact contract.
        """
        from mythril_tpu.ops import resident as RK
        from mythril_tpu.resilience.checkpoint import drain_requested

        _, jnp = _require_jax()
        assign = np.asarray(assign, dtype=np.int8)
        batch, V1 = assign.shape
        B = lane_bucket(batch)
        dispatch_stats.lane_slots_filled += batch
        dispatch_stats.lane_slots_total += B

        if drain_requested():
            # the resident solve is one indivisible dispatch, so the
            # drain seam sits at its boundary: bail before launching
            # and every lane retires undecided (CDCL tail / resumed
            # run finishes them, findings unchanged)
            obs.instant("dispatch.drain", cat="sweep", lanes=batch,
                        bucket=B)
            return np.zeros(batch, np.int32), np.array(assign, copy=True)

        pref_row = None
        if pref is not None:
            pref_row = np.zeros(V1, np.int8)
            n = min(V1, len(pref))
            pref_row[:n] = np.asarray(pref[:n], np.int8)
        seed = np.ones((B, V1), np.int8)
        seed[:batch] = assign
        state = RK.resident_state0(
            seed, batch, GATHER_DECISIONS, width=MAX_CLAUSE_WIDTH,
            pref_row=pref_row,
        )
        adj_dev = frontier["adj"]
        raw = self._cached_resident(V1 - 1)
        budget = RK.resident_budget()
        watchdog_limit = RK.resident_watchdog_limit()
        n_lane = len(RK.RESIDENT_LANE_FIELDS)
        with obs.wait("upload.lanes"):
            shared0 = [
                jnp.asarray(state[k]) for k in RK.RESIDENT_SHARED_FIELDS
            ]
        status_idx = RK.RESIDENT_LANE_FIELDS.index("status")

        def step_fn(lits_, *lane_vals):
            out = raw(lits_, adj_dev, *lane_vals, *shared0)
            lane_out, shared_out = out[:n_lane], out[n_lane:]
            # exit-reason telemetry per completed kernel invocation
            # (np.asarray blocks until the kernel finished — the wedge
            # point, so it stays inside the supervised region)
            with obs.wait("resident.fetch"):
                status = np.asarray(lane_out[status_idx])
                stall = int(np.asarray(shared_out[2])[0])
                iterations = int(np.asarray(shared_out[3])[0])
            reason = RK.exit_reason(status, stall, iterations,
                                    watchdog_limit, budget)
            dispatch_stats.resident_dispatches += 1
            dispatch_stats.resident_iterations += iterations
            obs.record_count("resident_iterations", iterations)
            counter = f"resident_exit_{reason}"
            setattr(dispatch_stats, counter,
                    getattr(dispatch_stats, counter) + 1)
            return lane_out

        live = np.arange(batch)
        key = f"resident:{B}"
        if obs.get_tracer().enabled:
            obs.counter("lanes.live", live=batch, bucket=B)
        lane_state = {k: state[k] for k in RK.RESIDENT_LANE_FIELDS}
        with obs.span("resident.solve", cat="sweep", key=key,
                      lanes=batch, bucket=B):
            lane_state, quarantined = self._dispatch_round(
                key, step_fn, lits, lane_state,
                RK.RESIDENT_LANE_FIELDS, live, frontier=True,
            )
        for local in quarantined:
            lane_state["status"][local] = 3  # undecided -> CDCL tail
        if quarantined:
            from mythril_tpu.observability.ledger import get_ledger

            get_ledger().count_transition("quarantined",
                                          len(quarantined))
        dispatch_stats.rounds += 1
        full_live = lane_state["fullsw"][:batch]
        steps_used = int(full_live.max()) if batch else 0
        dispatch_stats.device_sweeps += steps_used
        dispatch_stats.lane_sweeps_total += steps_used * B
        dispatch_stats.lane_sweeps_active += int(full_live.sum())
        dispatch_stats.frontier_steps += int(
            lane_state["fsteps"][:batch].sum()
        )
        self._harvest_round_learnts(lane_state, live, frontier)
        statuses_out = lane_state["status"][:batch].astype(np.int32)
        assign_out = lane_state["assign"][:batch].astype(np.int8)
        return (np.where(statuses_out == 3, 0, statuses_out),
                assign_out)

    def _solve_gather_ladder(self, key_base: str, lits, assign,
                             pref=None, frontier=None):
        """Round-laddered lockstep solve over assumption-seeded
        assignment vectors ``assign [batch, V1]`` (int8).

        ``pref`` (optional ``[V1]`` int8 row) is the warm-start
        decision-phase preference broadcast to every lane — see
        build_round_lane; it rides the lane state so re-packs carry it.

        ``frontier`` (optional dict with ``adj`` — the device
        adjacency index, ``ctx`` — the blast context for the
        learned-clause harvest, and ``col_to_var`` — the column→pool
        variable remap or None) switches the rounds to the
        event-driven frontier kernel (ops/frontier.py): per-lane
        recently-assigned queues carried across rounds and re-packs,
        adjacency-gather BCP between full sweeps, and in-kernel
        first-UIP clause learning harvested between rounds into the
        pool's nogood channel.  Watchdog/span keys become
        ``frontier:{budget}`` / ``frontier.round`` so the EWMA
        deadline model and the bench phase breakdown budget the new
        round shape separately from dense/gather rounds.  ``None``
        (or the ``MYTHRIL_TPU_FRONTIER=0`` kill switch upstream)
        runs the exact prior dense round kernels.

        Replaces the monolithic while_loop dispatch: budgeted rounds
        (GATHER_ROUND_BUDGETS), decided lanes retired between rounds,
        survivors re-packed into the smallest power-of-two lane bucket
        that fits.  Each round runs supervised under its own watchdog
        key ``{key_base}:{budget}`` so the latency-EWMA deadline model
        tracks the round's actual step budget, and each round fires the
        dispatch fault point (chaos tests exercise every rung through
        this path).

        A round whose dispatch fails *repeatably* (the retry rung
        exhausted) is bisected instead of demoting the context: halves
        of the live lanes re-dispatch (single attempt each, log2
        re-dispatches over the existing lane buckets) until the failing
        lane(s) are isolated and quarantined to the CDCL tail — see
        :meth:`_dispatch_round`.  Only when every lane fails alone does
        the ladder give up and raise DispatchAbandoned for the caller's
        context demotion, exactly as before.

        A drain request (resilience/checkpoint.py) is honored between
        rounds: survivors retire undecided so the analysis can land a
        final checkpoint instead of dying mid-dispatch.

        Returns (status[batch] int32 with bails mapped to undecided,
        final assign[batch, V1] int8).
        """
        from mythril_tpu.ops import frontier as FR
        from mythril_tpu.ops.resident import resident_kernel_enabled
        from mythril_tpu.resilience.checkpoint import drain_requested

        if frontier is not None and resident_kernel_enabled():
            # the persistent kernel subsumes the whole ladder below:
            # one dispatch, device-decided exit.  The multi-dispatch
            # code path stays byte-identical under the
            # MYTHRIL_TPU_RESIDENT_KERNEL=0 kill switch (and is the
            # only path with the frontier tier off — the resident
            # kernel is built from the frontier state layout).
            return self._solve_resident(key_base, lits, assign,
                                        pref=pref, frontier=frontier)

        _, jnp = _require_jax()
        assign = np.asarray(assign, dtype=np.int8)
        batch, V1 = assign.shape
        D = max(1, min(GATHER_DECISIONS, V1))
        B = lane_bucket(batch)
        dispatch_stats.lane_slots_filled += batch
        dispatch_stats.lane_slots_total += B

        pref_row = None
        if pref is not None:
            pref_row = np.zeros(V1, np.int8)
            n = min(V1, len(pref))
            pref_row[:n] = np.asarray(pref[:n], np.int8)
        if frontier is not None:
            seed = np.ones((B, V1), np.int8)
            seed[:batch] = assign
            state = FR.frontier_state0(
                seed, batch, GATHER_DECISIONS, width=MAX_CLAUSE_WIDTH,
                pref_row=pref_row,
            )
            order = FR.FRONTIER_STATE_FIELDS
            round_keys = ("fullsw", "fsteps", "nlearn")
            key_base = "frontier"
            span_name = "frontier.round"
            adj_dev = frontier["adj"]
        else:
            state = {
                "assign": np.ones((B, V1), np.int8),
                "lvl": np.zeros((B, V1), np.int32),
                "dvar": np.zeros((B, D), np.int32),
                "dphase": np.zeros((B, D), np.int8),
                "dflip": np.zeros((B, D), bool),
                "depth": np.zeros(B, np.int32),
                "status": np.zeros(B, np.int32),
                "step": np.zeros(B, np.int32),
                "pref": np.zeros((B, V1), np.int8),
            }
            order = ("assign", "lvl", "dvar", "dphase", "dflip", "depth",
                     "status", "step", "pref")
            round_keys = ("step",)
            span_name = "dispatch.round"
            state["assign"][:batch] = assign
            if pref_row is not None:
                state["pref"][:] = pref_row
            state["status"][batch:] = 3  # bucket pads: retired at step 0

        statuses_out = np.zeros(batch, np.int32)
        assign_out = np.array(assign, copy=True)
        live = np.arange(batch)

        budgets, spent, i = [], 0, 0
        while spent < GATHER_STEPS:
            budgets.append(
                GATHER_ROUND_BUDGETS[min(i, len(GATHER_ROUND_BUDGETS) - 1)]
            )
            spent += budgets[-1]
            i += 1

        for budget in budgets:
            if live.size == 0:
                break
            # counter tracks beside the span timeline: live lanes per
            # round, and — in frontier mode — how many recently-
            # assigned queue entries the event-driven rounds still hold
            # (the queue sum is only worth computing when it will land
            # on a timeline)
            if obs.get_tracer().enabled:
                obs.counter("lanes.live", live=int(live.size), bucket=B)
                if frontier is not None:
                    obs.counter(
                        "frontier.queue_depth",
                        queued=int(state["recent"][: live.size].sum()),
                    )
            if drain_requested():
                # cooperative drain checkpoint: abandon the remaining
                # rounds — survivors retire undecided (the CDCL tail or
                # the resumed run finishes them, findings unchanged).
                # Fires for a SIGTERM drain AND an expired per-request
                # budget (serve deadlines reach this exact seam); the
                # instant event puts the abandonment on the request's
                # span timeline / flight dump
                obs.instant("dispatch.drain", cat="sweep",
                            lanes=int(live.size), bucket=B)
                break
            for k in round_keys:  # per-round active/learn counters
                state[k][:] = 0
            if frontier is not None:
                raw = self._cached_frontier_round(V1 - 1, budget)
                step_fn = (
                    lambda lits_, *vals: raw(lits_, adj_dev, *vals)
                )
            else:
                step_fn = self._cached_round(V1 - 1, budget)
            with obs.span(span_name, cat="sweep",
                          key=f"{key_base}:{budget}",
                          lanes=int(live.size), bucket=B):
                state, quarantined = self._dispatch_round(
                    f"{key_base}:{budget}", step_fn, lits, state, order,
                    live, frontier=frontier is not None,
                )
            for local in quarantined:
                state["status"][local] = 3  # undecided -> CDCL tail
            if quarantined:
                # bisection cannot name the original state index from
                # down here, so quarantines land in the ledger as an
                # aggregate transition (the lanes themselves settle as
                # tail-demoted when the batch closes)
                from mythril_tpu.observability.ledger import get_ledger

                get_ledger().count_transition("quarantined",
                                              len(quarantined))
            dispatch_stats.rounds += 1
            if frontier is not None:
                # device_sweeps counts FULL sweeps only, so the
                # sweeps-per-lane headline stays comparable with the
                # dense ladder; the cheap adjacency-gather iterations
                # land in their own counter
                full_live = state["fullsw"][: live.size]
                steps_used = int(full_live.max()) if live.size else 0
                dispatch_stats.device_sweeps += steps_used
                dispatch_stats.lane_sweeps_total += steps_used * B
                dispatch_stats.lane_sweeps_active += int(full_live.sum())
                dispatch_stats.frontier_steps += int(
                    state["fsteps"][: live.size].sum()
                )
                self._harvest_round_learnts(state, live, frontier)
            else:
                steps_live = state["step"][: live.size]
                steps_used = int(steps_live.max()) if live.size else 0
                dispatch_stats.device_sweeps += steps_used
                dispatch_stats.lane_sweeps_total += steps_used * B
                dispatch_stats.lane_sweeps_active += int(steps_live.sum())
            st = state["status"][: live.size]
            done = st != 0
            if not done.any():
                continue
            for local in np.nonzero(done)[0]:
                statuses_out[live[local]] = st[local]
                assign_out[live[local]] = state["assign"][local]
            keep = np.nonzero(~done)[0]
            live = live[keep]
            if live.size == 0:
                break
            B_new = lane_bucket(int(keep.size))
            idx = np.concatenate(
                [keep, np.repeat(keep[:1], B_new - keep.size)]
            )
            for k in order:
                state[k] = np.ascontiguousarray(state[k][idx])
            state["status"][keep.size:] = 3
            if B_new < B:
                dispatch_stats.repacks += 1
            B = B_new
        # budget exhausted: survivors stay undecided with their final
        # (partial) assignment, exactly like the monolithic bail
        for local in range(live.size):
            statuses_out[live[local]] = state["status"][local]
            assign_out[live[local]] = state["assign"][local]
        return np.where(statuses_out == 3, 0, statuses_out), assign_out

    def _dispatch_round(self, key, step_fn, lits, state, order, live,
                        frontier: bool = False):
        """One supervised ladder round over ``state`` (bucket-sized
        arrays, rows < live.size live) with poisoned-lane bisection.

        The happy path is the classic retry rung.  When it exhausts
        (repeatable failure), the live lanes are bisected: each half
        re-dispatches once (no retries — the failure is already proven
        repeatable), failing halves split again, and lanes that fail
        alone are quarantined (returned for the caller to retire to the
        CDCL tail; ``quarantined_lanes``/``bisect_dispatches``
        telemetry).  The context stays on device.  Only when every lane
        fails alone — the failure is not lane-dependent — does the
        ladder escalate through watchdog.give_up (re-probe, demotion
        accounting, DispatchAbandoned) exactly like the pre-bisection
        ladder.

        Returns (state', quarantined local positions).
        """
        from mythril_tpu.resilience import faults
        from mythril_tpu.resilience.telemetry import resilience_stats
        from mythril_tpu.resilience.watchdog import (
            DispatchFailed, get_watchdog,
        )

        _, jnp = _require_jax()
        dog = get_watchdog()

        def attempt(sub_state, sub_ids, retries=None):
            with obs.wait("upload.lanes"):
                vals = [jnp.asarray(sub_state[k]) for k in order]

            def _thunk():
                faults.maybe_fault_dispatch(lane_ids=sub_ids)
                if frontier:
                    # the event-driven tier has its own injection point
                    # so the chaos suite covers the new dispatch shape
                    # (retry/bisect/demote rungs all reachable from it)
                    faults.maybe_fault_frontier()
                dispatch_stats.device_dispatch_calls += 1
                out = step_fn(lits, *vals)
                # the host copy blocks until the round finished — the
                # wedge point, so it belongs inside the supervision
                # (np.array, not asarray: the ladder mutates the state
                # between rounds and jax exports read-only views)
                with obs.wait("round.fetch"):
                    return [np.array(o) for o in out]

            return dict(zip(order, dog.run_attempts(
                key, _thunk, retries=retries
            )))

        batch_ids = [int(i) for i in live]
        try:
            return attempt(state, batch_ids), []
        except DispatchFailed as exc:
            last = exc.last
        n = int(live.size)
        if n == 1:
            # a single-lane batch cannot be bisected: lane poison and
            # device failure are indistinguishable — escalate
            dog.give_up(key, last)
        quarantined: List[int] = []

        def bisect(positions):
            resilience_stats.bisect_dispatches += 1
            B_sub = lane_bucket(len(positions))
            idx = np.concatenate(
                [positions,
                 np.repeat(positions[:1], B_sub - len(positions))]
            )
            sub = {k: np.ascontiguousarray(state[k][idx]) for k in order}
            sub["status"][len(positions):] = 3  # pads stay inert
            try:
                out = attempt(sub, [int(live[p]) for p in positions],
                              retries=0)
            except DispatchFailed:
                if len(positions) == 1:
                    quarantined.append(int(positions[0]))
                    return
                half = len(positions) // 2
                bisect(positions[:half])
                bisect(positions[half:])
                return
            for j, p in enumerate(positions):
                for k in order:
                    state[k][p] = out[k][j]

        half = n // 2
        bisect(np.arange(half))
        bisect(np.arange(half, n))
        if len(quarantined) == n:
            # every lane fails alone: the device (or this shape) is the
            # problem, not a lane — classic escalation
            dog.give_up(key, last)
        resilience_stats.quarantined_lanes += len(quarantined)
        log.warning(
            "poisoned-lane bisection on %s: quarantined %d/%d lanes to "
            "the CDCL tail; context stays on device", key,
            len(quarantined), n,
        )
        return state, quarantined

    def _build_cone_batch(self, ctx, assumption_sets):
        """Device inputs for the union-cone tier: (rows [N,K] int32
        with literals remapped to compact var ids, assign [B,n+1]
        int8, cone_vars [n] int64 original ids, roots key) — or None
        when the union cone exceeds the tier caps (or is empty).

        The cone walk + dedupe/remap (``_build_cone_rows``) is served
        by the cross-dispatch cone memo keyed on the union roots:
        sibling batches and repeat dispatches over an unchanged pool
        skip the host-side CSR work entirely; only the per-dispatch
        assumption columns are rebuilt here.

        Soundness matches the per-lane cone contract documented on
        BlastContext.cone: every shipped clause holds globally, so a
        kernel UNSAT is sound; a completed assignment is only a
        candidate and is verified against the original terms by the
        caller.  Clauses wider than MAX_CLAUSE_WIDTH are dropped
        (weakens BCP, never soundness)."""
        roots = tuple(
            sorted({lit for lane in assumption_sets for lit in lane})
        )
        if not roots:
            return None
        from mythril_tpu.ops.incremental import get_cone_memo

        built = get_cone_memo().get_or_build(
            ctx, ("cone_rows", roots),
            lambda: self._build_cone_rows(ctx, roots),
        )
        if built is None:
            return None
        rows, cone_vars, anchor = built
        n = int(cone_vars.size)
        assign = np.zeros((len(assumption_sets), n + 1), np.int8)
        assign[:, anchor] = 1
        for lane, assumptions in enumerate(assumption_sets):
            for lit in assumptions:
                var = abs(lit)
                pos = int(np.searchsorted(cone_vars, var))
                if pos < n and cone_vars[pos] == var:
                    assign[lane, pos + 1] = 1 if lit > 0 else -1
        dispatch_stats.h2d_bytes += int(assign.nbytes)
        return rows, assign, cone_vars, roots

    def _build_cone_rows(self, ctx, roots):
        """The memoized half of :meth:`_build_cone_batch`: cone walk,
        CSR fetch, width filter, compact remap, anchor row.  Returns
        (rows, cone_vars, anchor_column) or None when the cone exceeds
        the tier caps."""
        with obs.span("cone.build", cat="cone", roots=len(roots)):
            return self._build_cone_rows_inner(ctx, roots)

    def _build_cone_rows_inner(self, ctx, roots):
        try:
            clause_ids, cone_vars = ctx.pool.cone(list(roots))
        except Exception:  # noqa: BLE001 — optimization tier only
            return None
        if (
            clause_ids.size == 0
            or clause_ids.size > MAX_CONE_GATHER_CLAUSES
            or cone_vars.size > MAX_CONE_GATHER_VARS
        ):
            return None
        lits, indptr = ctx.pool.subset_csr(clause_ids)
        cone_vars = np.union1d(
            np.asarray(cone_vars, dtype=np.int64), [1]
        )  # the TRUE anchor must be mappable (see the synthetic row)
        n = int(cone_vars.size)
        widths = np.diff(indptr)
        keep = widths <= MAX_CLAUSE_WIDTH
        kept_widths = widths[keep]
        # bucket the row count to a power of two (all-zero rows are
        # inert padding for the kernels, same as DevicePool.refresh):
        # union cones change size every round, and an exact row count
        # would retrace the jitted solve / shard_map per dispatch
        row_count = DevicePool._bucket(int(keep.sum()) + 1)
        rows = np.zeros((row_count, MAX_CLAUSE_WIDTH), np.int32)
        if lits.size:
            mask = np.arange(MAX_CLAUSE_WIDTH)[None, :] < kept_widths[:, None]
            flat_keep = np.repeat(keep, widths)
            kept_lits = lits[flat_keep]
            pos = np.searchsorted(
                cone_vars, np.abs(kept_lits).astype(np.int64)
            )
            pos_clipped = np.minimum(pos, n - 1)
            if not np.all(cone_vars[pos_clipped] == np.abs(kept_lits)):
                # a subset clause references a var outside the walked
                # cone (late congruence attach): remapping it would be
                # silently unsound — decline the tier for this batch
                return None
            compact = pos + 1
            rows[: len(kept_widths)][mask] = np.where(
                kept_lits < 0, -compact, compact
            ).astype(np.int32)
        # synthetic anchor unit {TRUE}: guarantees a lane asserting the
        # FALSE literal conflicts in BCP instead of "completing"
        anchor = int(np.searchsorted(cone_vars, 1)) + 1
        rows[len(kept_widths), 0] = anchor
        return rows, cone_vars, anchor

    def check_cone_gather(self, ctx, assumption_sets):
        """Dispatch the batch against its union cone only.  Multi-
        device processes route through the dp x cp sharded mesh —
        this is the production path that puts mesh_dispatches on real
        analyze runs (VERDICT r4 #7); single-chip runs use the jitted
        lockstep step over the compact cone.  Returns per-lane
        verdicts like check_assumption_sets, or None when the cone
        does not fit the tier."""
        from mythril_tpu.resilience import faults
        from mythril_tpu.resilience.watchdog import (
            DispatchAbandoned, get_watchdog,
        )

        built = self._build_cone_batch(ctx, assumption_sets)
        if built is None:
            return None
        rows, assign, cone_vars, roots = built
        jax, jnp = _require_jax()
        n = int(cone_vars.size)
        self.device_engaged = True
        if len(jax.devices()) > 1:
            from mythril_tpu.parallel.mesh import (
                get_mesh, sharded_frontier_solve,
            )

            # the sharded path re-ships the cone rows per dispatch
            # (shard layout, not a resident buffer)
            dispatch_stats.h2d_bytes += int(rows.nbytes)

            def _solve_mesh_cone():
                faults.maybe_fault_dispatch()
                dispatch_stats.device_dispatch_calls += 1
                fa, st = sharded_frontier_solve(get_mesh(), rows, assign)
                return np.asarray(st), np.asarray(fa)

            try:
                status, final_assign = get_watchdog().supervised(
                    "mesh", _solve_mesh_cone
                )
            except DispatchAbandoned as exc:
                return self._abandon(ctx, exc, len(assumption_sets))
            dispatch_stats.mesh_dispatches += 1
            dispatch_stats.mesh_pool_rows = int(rows.shape[0])
            dispatch_stats.mesh_absorbed = getattr(
                ctx, "absorbed_learnt_count", 0
            )
        else:
            bucket = DevicePool._bucket(n)
            if bucket + 1 > assign.shape[1]:
                # nonexistent padding vars preassigned true: they must
                # never consume DPLL decisions (same rule as the
                # full-pool tier's `used` trick)
                assign = np.concatenate(
                    [assign,
                     np.ones((assign.shape[0],
                              bucket + 1 - assign.shape[1]), np.int8)],
                    axis=1,
                )
            # the cone rows stay resident across sibling dispatches:
            # the memo hands back the SAME device buffer while the
            # (generation, pool_version, roots) scope holds, so a
            # repeat dispatch uploads only the assumption columns
            from mythril_tpu.ops.incremental import get_cone_memo

            def _upload_rows():
                dispatch_stats.h2d_bytes += int(rows.nbytes)
                with obs.span("upload.cone_rows", cat="h2d",
                              bytes=int(rows.nbytes)), \
                        obs.wait("upload.cone_rows"):
                    return jnp.asarray(rows)

            rows_dev = get_cone_memo().get_or_build(
                ctx, ("cone_dev", roots), _upload_rows
            )
            # frontier tier over the cone rows: the adjacency index is
            # memoized beside the rows (same (generation, pool_version,
            # learned-generation) scope), and learned-clause literals
            # remap from compact cone columns back to pool variable
            # ids before the harvest (column i+1 = cone_vars[i])
            from mythril_tpu.ops.frontier import (
                build_adjacency, frontier_enabled,
            )

            frontier = None
            if frontier_enabled():
                def _upload_adj():
                    adj = build_adjacency(rows, assign.shape[1])
                    dispatch_stats.h2d_bytes += int(adj.nbytes)
                    with obs.span("upload.adjacency", cat="h2d",
                                  bytes=int(adj.nbytes)), \
                            obs.wait("upload.adjacency"):
                        return jnp.asarray(adj)

                adj_dev = get_cone_memo().get_or_build(
                    ctx, ("cone_adj", roots), _upload_adj
                )
                col_to_var = np.zeros(n + 1, np.int64)
                col_to_var[1:] = cone_vars
                frontier = {"adj": adj_dev, "ctx": ctx,
                            "col_to_var": col_to_var}
            try:
                status, final_assign = self._solve_gather_ladder(
                    "cone", rows_dev, assign,
                    pref=warm_pref_row(
                        ctx, assign.shape[1], cone_vars=cone_vars,
                        offset=1, lanes=len(assumption_sets),
                    ),
                    frontier=frontier,
                )
            except DispatchAbandoned as exc:
                return self._abandon(ctx, exc, len(assumption_sets))
        status, final_assign = faults.maybe_corrupt_lanes(
            status, final_assign
        )
        # expand the compact assignment back to full var space so the
        # caller's model extraction works unchanged
        V1 = ctx.solver.num_vars + 1
        full = np.zeros((len(assumption_sets), V1), np.int8)
        full[:, cone_vars] = final_assign[:, 1:n + 1]
        self.last_assignments = full
        return [
            False if status[lane] == 2 else None
            for lane in range(len(assumption_sets))
        ]

    def _gather_eligibility(self, ctx):
        """Shared gather-path gates for the sync and async dispatchers.
        Returns (skip_counter_name | None, num_vars): None means
        eligible.  Size reasoning: the gather probe scans the WHOLE
        pool per BCP iteration — past a few thousand clauses it costs
        orders of magnitude more than the incremental CDCL it is
        trying to save (measured ~45 s/dispatch at 76k clauses vs ~ms
        per CDCL query), so big pools go straight to the CDCL tail.
        Absorbed learnt clauses (folded in here, BEFORE the budget
        check, so the count the gate sees is what the kernel scans)
        get a bounded budget exemption — sharing them must not shut
        the device off, but an unbounded exemption would let the total
        pool regrow the pathology."""
        from mythril_tpu.ops.device_health import backend_name, device_ok
        from mythril_tpu.ops.pallas_prop import pallas_enabled

        num_vars = ctx.solver.num_vars
        if not device_ok():
            return "unhealthy_skips", num_vars
        if pallas_enabled() is None and backend_name() == "cpu":
            # auto mode on a CPU-only host: a gather dispatch through
            # the CPU jax backend costs more than the CDCL tail it
            # replaces (measured +4-6s over the corpus).  Real
            # accelerators keep the path; tests reach it on CPU by
            # setting MYTHRIL_TPU_PALLAS explicitly.
            return "cpu_auto_skips", num_vars
        if num_vars > MAX_GATHER_VARS:
            return "size_bailouts", num_vars
        ctx.absorb_learnts(max_width=MAX_CLAUSE_WIDTH)
        absorbed = min(
            getattr(ctx, "absorbed_learnt_count", 0), MAX_LEARNT_EXEMPTION
        )
        if ctx.pool.num_clauses - absorbed > MAX_GATHER_CLAUSES:
            return "size_bailouts", num_vars
        return None, num_vars

    def _sync_pool_and_assign(self, ctx, assumption_sets, num_vars):
        """Shared prep for the sync and async gather paths: reflect the
        pool delta on device and build the assumption-seeded assignment
        matrix."""
        from mythril_tpu.ops.incremental import resident_pool_enabled

        _require_jax()
        if self.pool_generation != ctx.generation or (
            not resident_pool_enabled()
        ):
            # a new BlastContext (reset between analyses): the resident
            # pool describes a different formula — appending would graft
            # the new clauses onto it at stale offsets and make device
            # UNSAT verdicts unsound, so always rebuild from scratch.
            # The MYTHRIL_TPU_RESIDENT_POOL=0 kill switch takes the same
            # path every dispatch: full rebuild + full upload (the
            # pre-incremental behavior, for A/B attribution runs).
            self.pool.refresh(ctx, num_vars)
            self.pool.version = ctx.pool_version
            self.pool_generation = ctx.generation
        elif self.pool.version != ctx.pool_version or (
            self.pool.num_vars < num_vars
        ):
            # delta append into the existing buckets when possible; full
            # rebuild + upload only when a bucket grows (repack) or the
            # resident mirror was invalidated
            if not self.pool.append(ctx, num_vars):
                self.pool.refresh(ctx, num_vars)
            self.pool.version = ctx.pool_version

        batch = len(assumption_sets)
        V1 = self.pool.num_vars + 1
        assign = np.zeros((batch, V1), dtype=np.int8)
        # vars absent from every retained clause (bucket padding, vars
        # defined only by dropped wide clauses) are preassigned so the
        # DPLL never spends decisions completing them; assumptions below
        # overwrite where they refer to such a var
        assign[:, ~self.pool.used] = 1
        assign[:, 1] = 1  # constant-TRUE anchor
        for lane, assumptions in enumerate(assumption_sets):
            for lit in assumptions:
                var = abs(lit)
                if var < V1:
                    assign[lane, var] = 1 if lit > 0 else -1
        # with the pool resident, the assumption matrix IS the
        # per-dispatch payload (plus lane descriptors); count it
        dispatch_stats.h2d_bytes += int(assign.nbytes)
        return assign

    def prepare_gather(self, ctx, assumption_sets):
        """Async-prefetch preparation (ops/async_dispatch.py): run the
        sync path's eligibility gates (minus the profit gate) and build
        the device inputs ON THE CALLING THREAD — everything that
        touches the blast context — then return a zero-argument runner
        that returns the solve's round generator
        (:meth:`_prefetch_rounds`; its first round compiles once per
        pool bucket).  The runner is safe to execute on a worker
        thread: it captures immutable jax arrays and plain numpy, and
        the host thread never waits on it.  Returns None when the
        frontier is ineligible."""
        if not assumption_sets:
            return None
        verdict, num_vars = self._gather_eligibility(ctx)
        if verdict == "size_bailouts":
            # the prefetch channel must not go dark in the oversized-
            # pool regime the cone tier serves (deep analyses live
            # there): prepare a cone-tier runner instead
            built = self._build_cone_batch(ctx, assumption_sets)
            if built is None:
                return None
            rows, assign, cone_vars, _roots = built
            n = int(cone_vars.size)
            bucket = DevicePool._bucket(n)
            if bucket + 1 > assign.shape[1]:
                assign = np.concatenate(
                    [assign,
                     np.ones((assign.shape[0],
                              bucket + 1 - assign.shape[1]), np.int8)],
                    axis=1,
                )
            full_width = ctx.solver.num_vars + 1
            # counted here, at the launch, on the analysis thread: the
            # worker may get to its run after this analysis is over
            dispatch_stats.h2d_bytes += int(rows.nbytes)
            dispatch_stats.device_dispatch_calls += 1

            def run_cone():
                # worker-thread upload (never through the shared memo:
                # the host could be mutating it concurrently);
                # cone_vars/full_width let the harvester expand the
                # compact assignment back to full var space
                return self._prefetch_rounds(
                    bucket, rows, assign,
                    cone_vars=cone_vars, full_width=full_width,
                )

            return run_cone
        if verdict is not None:
            return None
        assign = self._sync_pool_and_assign(ctx, assumption_sets, num_vars)
        bucket = self.pool.num_vars
        lits = self.pool.lits  # immutable jax array: safe to capture
        dispatch_stats.device_dispatch_calls += 1  # at the launch

        def run():
            # first compile for this bucket happens on the worker
            # thread — the host's only budget here is idle time
            return self._prefetch_rounds(bucket, lits, assign)

        return run


_backend: Optional[BatchedSatBackend] = None


def get_backend() -> BatchedSatBackend:
    global _backend
    if _backend is None:
        _backend = BatchedSatBackend()
    return _backend


def reset_resident_pools() -> None:
    """Invalidate every process-global device-resident structure: the
    gather tier's resident clause pool and the cross-dispatch cone
    memo.  Called by the checkpoint plane on resume — the resumed
    process re-interns nodes and re-blasts literals, so clause indices
    and literal numbering never match what an earlier pool upload (or
    memoized cone layout) described; serving them would be silently
    unsound, not just stale.  The word tier's programs and verdict
    memos are keyed on interned node ids and die for the same reason."""
    from mythril_tpu.ops.incremental import reset_cone_memo
    from mythril_tpu.smt.word_tier import reset_word_tier

    if _backend is not None:
        _backend.pool = DevicePool()
        _backend.pool_generation = -1
    reset_cone_memo()
    reset_word_tier()
    # the sharded-mesh caches hold a Mesh over a device topology and
    # jitted shard_map solves keyed by id(mesh): a checkpoint resume or
    # serve decontamination that kept them could serve a solve compiled
    # for a dead topology (or collide on a recycled mesh id) — drop
    # them with everything else device-resident
    from mythril_tpu.parallel.mesh import reset_mesh_caches

    reset_mesh_caches()
    # the veritesting join-point memo is keyed by bytecode string but
    # caches SegmentPlan-derived pc sets — dropped with the plan cache
    # family so a resumed process rebuilds them from its own disassembly
    from mythril_tpu.laser.ethereum.veritest import reset_veritest_memos

    reset_veritest_memos()


def batch_check_states(constraint_sets) -> List[Optional[bool]]:
    """Feasibility verdicts for a frontier of constraint sets.

    True = SAT (model verified against the term constraints),
    False = UNSAT (sound), None = undecided (caller uses CDCL).

    Phases (cheapest decision procedure first):

    1. structural: constraints folded to literal False;
    2. host word-level probing per lane (shared ``recent_models``, so a
       model found for one lane immediately serves its siblings) — this
       decides the easy-SAT majority in microseconds per lane and keeps
       them off the device entirely;
    3. device dense-cone BCP over the probe-resistant residue — where
       the prunable (UNSAT) lanes live;
    4. anything still open returns None for the caller's CDCL tail.
    """
    from mythril_tpu.smt import terms as T
    from mythril_tpu.smt.solver import get_blast_context
    from mythril_tpu.support.support_args import args

    ctx = get_blast_context()
    from mythril_tpu.ops.async_dispatch import get_async_dispatcher

    # consume any finished async prefetch first: its UNSAT memos and
    # remembered models decide lanes of THIS frontier below for free
    get_async_dispatcher().harvest(ctx)
    # per-lane attribution ledger (observability/ledger.py): every lane
    # entering this funnel gets a lifecycle record; the try/finally
    # guarantees conservation — whatever the funnel leaves undecided
    # settles as tail-demoted when the batch closes
    from mythril_tpu.observability.ledger import get_ledger

    lanes_led = get_ledger().begin_batch(
        "batch_check", len(constraint_sets)
    )
    try:
        return _batch_check_states_inner(
            ctx, constraint_sets, lanes_led
        )
    finally:
        lanes_led.close()


def _batch_check_states_inner(ctx, constraint_sets, lanes_led):
    from mythril_tpu.smt import terms as T
    from mythril_tpu.support.support_args import args

    from mythril_tpu.ops.async_dispatch import get_async_dispatcher

    node_sets: List[Optional[List]] = []
    decided: List[Optional[bool]] = [None] * len(constraint_sets)

    for i, constraints in enumerate(constraint_sets):
        nodes = []
        falsy = False
        for c in constraints:
            if isinstance(c, bool):
                if not c:
                    falsy = True
                    break
                continue
            node = c.raw if hasattr(c, "raw") else c
            if node is T.FALSE:
                falsy = True
                break
            if node is T.TRUE:
                continue
            nodes.append(node)
        if falsy:
            decided[i] = False
            node_sets.append(None)
            lanes_led.decide(i, "structural", "unsat")
        else:
            node_sets.append(nodes)

    # autopilot (mythril_tpu/autopilot): per-lane feature extraction +
    # routing from the ledger-fed cost model.  Feature vectors are
    # stamped on the ledger records (artifact schema v2) so this batch
    # is replayable offline; a decision only skips tiers whose work the
    # host CDCL tail redoes soundly — verdict logic is untouched, and
    # MYTHRIL_TPU_AUTOPILOT=0 makes this a row of Nones
    from mythril_tpu.autopilot import route_lanes

    routes = route_lanes(node_sets, lanes_led)

    # host word-level probe: evaluation against candidate models is a
    # full verification, so a hit is a sound SAT verdict.  Results are
    # memoized on the context (shared with the CDCL tail): SAT is
    # permanent; a failed probe is retried only after a new model lands
    # in recent_models (frontiers repeat constraint sets across rounds,
    # so re-probing measured ~20% of corpus wall-clock)
    from mythril_tpu.smt.solver import SolverStatistics
    from mythril_tpu.support.model import peek_model_verdict

    stats = SolverStatistics()
    with obs.span("solver.probe", sink=(stats, "probe_s"), cat="solver",
                  lanes=len(node_sets)) as probe_span:
        for i, nodes in enumerate(node_sets):
            if nodes is None:
                continue
            if ctx.unsat_memo_hit(tuple(sorted(n.id for n in nodes))):
                decided[i] = False  # permanent verdict (see BlastContext)
                lanes_led.decide(i, "probe", "unsat")
                continue
            # the per-query funnel may have solved this exact set
            # already (frontier sets repeat across rounds); a cached
            # verdict beats re-probing against the rotating
            # recent-model set
            cached = peek_model_verdict(constraint_sets[i])
            if cached is not None:
                decided[i] = cached
                lanes_led.decide(i, "probe",
                                 "sat" if cached else "unsat")
                continue
            if not getattr(args, "word_probing", True):
                continue
            if ctx.probe_with_memo(nodes) is not None:
                decided[i] = True
                dispatch_stats.host_probe_sat += 1
                lanes_led.decide(i, "probe", "sat")
    lanes_led.tier_wall("probe", probe_span.elapsed_s)

    # word-level tier (smt/word_tier.py): batched interval + known-bits
    # propagation over the whole open frontier — interval-UNSAT and
    # constant-fold lanes retire HERE, before any CNF exists; surviving
    # lanes keep their per-variable known bits, which become unit
    # assumption literals below (smaller effective cones, free BCP)
    word_hints: List[Optional[dict]] = [None] * len(node_sets)
    from mythril_tpu.smt.word_tier import (
        get_word_tier, hint_literals, word_tier_enabled,
    )

    if word_tier_enabled():
        # lanes the autopilot routed past the word tier (signatures it
        # never decides) stay out of the propagation batch entirely
        open_sets: List[Optional[List]] = [
            nodes if decided[i] is None and not (
                routes[i] is not None and routes[i].skip_word
            ) else None
            for i, nodes in enumerate(node_sets)
        ]
        import time as _time

        word_t0 = _time.perf_counter()
        word_verdicts, word_hints, word_envs = get_word_tier().decide(
            ctx, open_sets
        )
        lanes_led.tier_wall("word", _time.perf_counter() - word_t0)
        for i, verdict in enumerate(word_verdicts):
            if verdict is None or decided[i] is not None:
                continue
            decided[i] = verdict
            lanes_led.decide(i, "word", "sat" if verdict else "unsat")
            if verdict and word_envs[i] is not None:
                # a verified word-tier model serves sibling probes the
                # same way a CDCL model would (no literal truth row, so
                # it stays out of the warm-start channel)
                ctx._remember_model(word_envs[i])

    proof_log = getattr(args, "proof_log", False)
    # --proof-log no longer disables the accelerator (VERDICT r4 #6):
    # device SAT lanes were always certificate-clean (the model is
    # verified by term evaluation before it decides anything), and
    # device UNSAT lanes are now host-confirmed by a bounded CDCL solve
    # of the same cube BEFORE they decide a state — the confirming
    # solve records the ASSUMPTION_CONFLICT proof event that makes the
    # verdict independently checkable (smt/drat.py).  A wrong device
    # UNSAT cannot ship: it would fail confirmation and leave the lane
    # to the authoritative CDCL tail.

    open_indices = [i for i, d in enumerate(decided) if d is None]
    # tail-direct lanes skip the device pipeline entirely: the CDCL
    # tail answers them with full budget either way, so the only change
    # is not paying blast/dispatch for a predicted-doomed lane (the
    # ledger already carries their routed_by stamp; they settle as
    # tail-demoted at batch close like any undecided lane)
    if any(r is not None and r.skip_device for r in routes):
        open_indices = [
            i for i in open_indices
            if not (routes[i] is not None and routes[i].skip_device)
        ]
    if len(open_indices) < effective_min_lanes():
        return decided

    # blast only the still-open lanes (probe-decided lanes must not grow
    # the clause pool, and an op outside the blaster's fragment should
    # just leave its lane to the CDCL tail, not fail the batch).  Each
    # lane's word-tier known bits ride along as unit assumption
    # literals: they are implied by the lane's own constraints, so
    # satisfiability is untouched, but the device DPLL starts with the
    # pinned bits pre-assigned and the CDCL propagates them for free
    assumption_sets: List[Optional[List[int]]] = [None] * len(node_sets)
    for i in list(open_indices):
        try:
            lits = [ctx.blast_lit(n) for n in node_sets[i]]
            if word_hints[i]:
                lits.extend(hint_literals(ctx, word_hints[i]))
            assumption_sets[i] = list(dict.fromkeys(lits))
        except NotImplementedError:
            decided[i] = None
            open_indices.remove(i)
            # a term outside the blaster's fragment: the lane can never
            # reach a device tier — mark it opaque on its way to the
            # tail so the artifact explains the demotion
            lanes_led.transition(i, "opaque")
    if len(open_indices) < 2:
        return decided

    # dedupe identical assumption sets: sibling states forked in the
    # same VM step often share most (sometimes all) constraints
    unique: Dict[Tuple[int, ...], int] = {}
    rep_indices: List[int] = []
    lane_of: List[int] = []
    for i in open_indices:
        lits_key = tuple(sorted(assumption_sets[i]))
        lane = unique.get(lits_key)
        if lane is None:
            lane = len(rep_indices)
            unique[lits_key] = lane
            rep_indices.append(i)
        lane_of.append(lane)

    if not getattr(args, "device_force_dispatch", False):
        # adaptive profit gate: the dispatch pays 0.3-2.4 s (cone +
        # build + compile-amortized solve); skip it whenever the tuned
        # CPU stack is projected to clear the residue for less.  The
        # projection uses the analysis's own observed native CDCL cost
        # so the policy tracks the workload, not a constant.
        stats = SolverStatistics()
        avg_native = (
            stats.native_s / stats.native_calls
            if getattr(stats, "native_calls", 0) else 0.0
        )
        projected = len(rep_indices) * avg_native
        if projected < getattr(args, "device_min_save_s", 0.5):
            dispatch_stats.profit_skips += 1
            if (
                getattr(args, "async_dispatch", True)
                # a demoted context must not keep feeding the wedged
                # device through the prefetch side door
                and get_backend().fused_generation != ctx.generation
            ):
                # not worth BLOCKING for — but the device is idle, so
                # prefetch the batch asynchronously: refutations and
                # models harvested on a later call only have to beat
                # idle time, not CPU time.  Queued (coalesce-deferred)
                # lanes ride along to fill the prefetch bucket.
                from mythril_tpu.ops.coalesce import get_coalescer

                extras = get_coalescer().drain(ctx)
                launched = get_async_dispatcher().launch(
                    get_backend(), ctx,
                    [assumption_sets[i] for i in rep_indices]
                    + [q.lits for q in extras],
                    [node_sets[i] for i in rep_indices]
                    + [q.nodes for q in extras],
                    [constraint_sets[i] for i in rep_indices]
                    + [q.constraints for q in extras],
                )
                if extras:
                    if launched:
                        dispatch_stats.coalesced_dispatches += 1
                        dispatch_stats.coalesced_lanes += len(extras)
                    else:
                        get_coalescer().requeue(ctx, extras)
            return decided

    backend = get_backend()
    fuse_retry_attempt = False
    if backend.futile_ctx_generation != ctx.generation:
        backend.futile_ctx_generation = ctx.generation
        backend.futile_dispatches = 0
        backend.fused_skips = 0
        backend.fuse_retries = 0
        backend.fuse_was_slow = False
        dispatch_stats.fused = False  # stat mirrors the re-armed fuse
    if backend.fused_generation == ctx.generation:
        # adaptive fuse blown: earlier dispatches in this context kept
        # deciding nothing, so the frontier goes straight to the tail —
        # but the workload shape changes as execution advances (e.g.
        # SAT-heavy dispatch-tree rounds give way to dead-path guard
        # rounds that batched BCP kills in bulk), so a bounded number
        # of periodic retry dispatches probe whether the device has
        # started paying; a deciding retry re-arms the fuse fully.
        backend.fused_skips += 1
        if (
            backend.fuse_was_slow  # each retry could stall 10s+ again
            or backend.fuse_retries >= MAX_FUSE_RETRIES
            or backend.fused_skips % FUSE_RETRY_PERIOD != 0
        ):
            dispatch_stats.fuse_skips += 1
            obs.record_count("fuse_skips")
            return decided
        backend.fuse_retries += 1
        fuse_retry_attempt = True
    # Full DPLL search always: unlike the round-2 WalkSAT (which only
    # retried the models the host probe had just failed), the decision
    # search explores assignments the probe never saw, so it stays on
    # even for probe-filtered residues — that residue is exactly where
    # the device must pay.
    from mythril_tpu.ops.coalesce import get_coalescer

    rep_sets = [assumption_sets[i] for i in rep_indices]
    admitted = get_coalescer().admit(
        ctx, rep_sets,
        [node_sets[i] for i in rep_indices],
        [constraint_sets[i] for i in rep_indices],
        force_now=fuse_retry_attempt,
    )
    if admitted is None:
        # coalescing window: this underfilled batch waits in the
        # admission queue; its lanes fall through to the CDCL tail
        # this round (verdicts unchanged — exactly what an undecided
        # device lane does) and a later merged dispatch pays them
        # back through the memo/nogood channel
        lanes_led.transition_open(open_indices, "deferred")
        return decided
    extras = admitted
    prefetch_inflight = get_async_dispatcher().pending is not None
    # ledger: the open lanes enter a device tier now.  Which kernel
    # family answers (event-driven frontier rounds vs dense full-batch
    # sweeps) follows the frontier kill switch — the same branch the
    # ladder itself takes
    from mythril_tpu.ops.frontier import frontier_enabled
    from mythril_tpu.resilience.watchdog import compile_clock

    device_tier = "frontier" if frontier_enabled() else "sweep"
    lanes_led.transition_open(open_indices, "dispatched")
    sweeps_before = dispatch_stats.device_sweeps
    learned_before = dispatch_stats.learned_clauses
    compiled_before = compile_clock().total()
    # the span is the timing primitive: it feeds device_s whether or
    # not tracing is on, and lands on the --trace-out timeline when it
    # is (observability/spans.py), so the two can never disagree
    with obs.span("dispatch.batch_check",
                  sink=(dispatch_stats, "device_s"), cat="dispatch",
                  lanes=len(rep_sets) + len(extras)) as dispatch_span:
        verdicts = backend.check_assumption_sets(
            ctx, rep_sets + [q.lits for q in extras],
        )
    dispatch_elapsed = dispatch_span.elapsed_s
    compile_s = compile_clock().total() - compiled_before
    lanes_led.tier_wall(device_tier, dispatch_elapsed)
    lanes_led.add_sweeps(
        device_tier, dispatch_stats.device_sweeps - sweeps_before
    )
    lanes_led.add_learned(
        dispatch_stats.learned_clauses - learned_before
    )
    # attribution counters tally only real device (or interpret-mode
    # kernel) passes — a bail-out to the CDCL tail is not a dispatch
    engaged = getattr(backend, "device_engaged", False)
    if fuse_retry_attempt and not engaged:
        # the retry never reached a device (size caps / health bailout)
        # — refund it, the device was not actually re-probed
        backend.fuse_retries -= 1
    if engaged:
        dispatch_stats.dispatches += 1
        dispatch_stats.lanes += len(rep_indices) + len(extras)
        if extras:
            dispatch_stats.coalesced_dispatches += 1
            dispatch_stats.coalesced_lanes += len(extras)

    counted_lanes = set()  # per-verdict counters tally device lanes,
    # not original states (several states can share one deduped lane)
    lane_confirmations: Dict[int, bool] = {}  # proof-log: lane -> certified
    device_decided = 0  # lanes THIS dispatch decided (fuse accounting)
    for pos, i in enumerate(open_indices):
        lane = lane_of[pos]
        first_for_lane = engaged and lane not in counted_lanes
        counted_lanes.add(lane)
        verdict = verdicts[lane]
        if verdict is False:
            if proof_log:
                # certify before deciding: one bounded host solve per
                # deduped lane; its UNSAT answer records the proof
                # event (see BlastContext.confirm_unsat)
                confirmed = lane_confirmations.get(lane)
                if confirmed is None:
                    confirmed = ctx.confirm_unsat(
                        assumption_sets[rep_indices[lane]]
                    )
                    lane_confirmations[lane] = confirmed
                if not confirmed:
                    decided[i] = None  # tail re-solves with full budget
                    continue
            decided[i] = False
            lanes_led.decide(i, device_tier, "unsat")
            # device UNSAT is permanent (the pool only gains implied
            # clauses): memoize the verdict and learn the assumption
            # nogood so the CDCL and future dispatches inherit the
            # refutation — the cross-dispatch learning channel
            ctx.note_unsat(node_sets[i])
            if first_for_lane:
                ctx.learn_nogood(
                    assumption_sets[rep_indices[lane]],
                    certified=proof_log,
                )
                dispatch_stats.unsat += 1
                device_decided += 1
            continue
        # candidate lane: verify the (possibly partial) assignment by
        # evaluating the original terms; unassigned leaves default 0
        env = _env_from_assignment(ctx, backend.last_assignments[lane])
        ok = True
        for c in constraint_sets[i]:
            node = c.raw if hasattr(c, "raw") else c
            if isinstance(node, bool):
                continue
            if T.evaluate(node, env) is not True:
                ok = False
                break
        decided[i] = True if ok else None
        if ok:
            # a host-verified model: attributed to the device tier that
            # produced the assignment, or to the probe tier when the
            # dispatch bailed out and the zero assignment happened to
            # verify (host evaluation did the deciding then)
            lanes_led.decide(
                i, device_tier if engaged else "probe", "sat"
            )
        if first_for_lane:
            if ok:
                # a verified device model serves future host probes the
                # same way a CDCL model would; the literal-level truth
                # row tags it for warm starts (phase saving across the
                # fork tree — ops/incremental.py)
                ctx._remember_model(
                    env, truth=backend.last_assignments[lane]
                )
                dispatch_stats.sat_verified += 1
                device_decided += 1
            else:
                dispatch_stats.undecided += 1
    # coalesced extras: lanes merged from the admission queue were
    # already answered by the CDCL tail in their own (deferred) round,
    # so their device verdicts land in the memo/model channels only —
    # the same contract the async harvest uses
    n_rep = len(rep_indices)
    for pos, q in enumerate(extras):
        verdict = verdicts[n_rep + pos]
        if verdict is False:
            if proof_log and not ctx.confirm_unsat(q.lits):
                continue
            ctx.note_unsat(q.nodes)
            if engaged:
                ctx.learn_nogood(q.lits, certified=proof_log)
                dispatch_stats.unsat += 1
                device_decided += 1
        elif engaged:
            env = _env_from_assignment(
                ctx, backend.last_assignments[n_rep + pos]
            )
            ok = True
            for c in q.constraints:
                node = c.raw if hasattr(c, "raw") else c
                if isinstance(node, bool):
                    continue
                if T.evaluate(node, env) is not True:
                    ok = False
                    break
            if ok:
                ctx._remember_model(
                    env, truth=backend.last_assignments[n_rep + pos]
                )
                dispatch_stats.sat_verified += 1
                device_decided += 1
    if engaged:
        # adaptive fuse accounting: a dispatch "paid off" iff it decided
        # at least one lane (device UNSAT, or a device model that
        # host-verified).  Consecutive zero-yield dispatches mean the
        # workload shape is wrong for the device — stop paying kernel
        # latency for it in this context.
        if device_decided:
            backend.futile_dispatches = 0
            if backend.fused_generation == ctx.generation:
                # a retry paid off: the workload shape changed, re-arm
                # fully (including the retry budget — each productive
                # phase earns the next fuse its own retries)
                backend.fused_generation = -1
                backend.fused_skips = 0
                backend.fuse_retries = 0
                dispatch_stats.fused = False
                log.info("device dispatch re-armed: retry decided %d lanes",
                         device_decided)
        else:
            backend.futile_dispatches += 1
            # a prefetch kernel in flight shares the device: its queue
            # time inflates this dispatch, so don't let it trip the
            # slow fuse (the prefetch is the idle-time use the fuse
            # exists to protect).  Compile time is one-off set-up, not
            # the device's speed: a cold compile alone fused a v5e off
            # after one dispatch, so a rerun from a warm cache took a
            # different path (PR 21)
            slow = (
                dispatch_elapsed - compile_s > SLOW_DISPATCH_FUSE_S
                and not prefetch_inflight
            )
            if slow:
                # one slow zero-yield dispatch (a struggling device or
                # a workload wrong for it) is already worse than the
                # whole CDCL tail — don't wait for two more
                backend.futile_dispatches = FUTILE_DISPATCH_FUSE
                backend.fuse_was_slow = True
            already_fused = backend.fused_generation == ctx.generation
            if backend.futile_dispatches >= FUTILE_DISPATCH_FUSE:
                backend.fused_generation = ctx.generation
                dispatch_stats.fused = True
                if already_fused:
                    log.debug("fuse retry dispatch yielded nothing")
                elif slow:
                    log.info(
                        "device dispatch fused off: zero-decision "
                        "dispatch took %.1fs (%.1fs of it compiling)",
                        dispatch_elapsed, compile_s,
                    )
                else:
                    log.info(
                        "device dispatch fused off: %d consecutive "
                        "zero-decision dispatches",
                        backend.futile_dispatches,
                    )
    return decided


def _env_from_assignment(ctx, assignment: np.ndarray):
    """Build an EvalEnv from a device assignment vector — one
    vectorized decode shared with the native-model path
    (BlastContext.extract_env)."""
    return ctx.extract_env(assignment)
