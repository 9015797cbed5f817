"""Device-native propagation: implication frontier, adjacency-gather
BCP, and on-device first-UIP clause learning.

BENCH_r05's span breakdown showed the dominant remaining waste is
dense sweeping: 9,698 full-batch device sweeps to decide 158 lanes
(~61 sweeps/lane), because every round re-reads *every* clause row
even though almost none are adjacent to newly-assigned literals.
SatIn (arxiv 2303.02588) and the FPGA BCP accelerator study (arxiv
2401.07429) both conclude that inference throughput comes from
touching only clauses watching recently-assigned literals.  This
module is that design for the gather-tier round ladders:

- **Adjacency index** (:func:`build_adjacency`): a literal→clause-row
  index built once per upload from the same ``[C, K]`` clause rows the
  kernels sweep — ``adj[v]`` holds (up to a degree cap) the rows in
  which variable ``v`` occurs.  Ships to the device alongside the
  resident pool and is invalidated with it.

- **Frontier rounds** (:func:`build_frontier_rounds`): each lane
  carries a "recently assigned" variable queue (``recent [B, V1]``)
  across sweeps, rounds AND bucket re-packs.  Most iterations gather
  only the clause rows adjacent to queued variables (``fan`` vars ×
  ``deg`` rows — a few hundred rows instead of the whole pool);
  a full sweep runs only when every live queue is drained (a decision
  or completion needs the whole-pool view) or every ``period``-th
  iteration as a safety net.  Soundness is preserved by construction:
  conflicts/forcings found in gathered rows are real pool clauses, so
  acting on them is sound unconditionally; decisions, the don't-care
  cascade and SAT completion are gated on full sweeps (complete
  views), and SAT candidates are host-verified anyway.  A truncated
  adjacency list (degree past the cap) can only *delay* a unit to the
  next full sweep, never forge a verdict.

- **First-UIP learning** (in-kernel): the frontier kernel tracks the
  implication trail (``reason``/``tpos``/``lvl`` planes — the row that
  forced each variable, its assignment stamp, its decision level).  On
  a conflict with decisions on the stack it resolves the conflicting
  row against reason rows in reverse trail order until one literal of
  the conflict level remains (the first unique implication point) and
  emits the learned clause into a bounded per-lane buffer.  Learned
  clauses are derived purely by resolution over pool rows, so they are
  implied by the pool and valid for EVERY lane; the host harvests them
  between rounds into the blast context's nogood channel
  (:meth:`BlastContext.harvest_device_clauses`), from where they reach
  the native CDCL immediately and the device-resident pool as
  append-only delta uploads on the next dispatch (ops/incremental.py).
  The search itself still backtracks chronologically — learning adds
  pruning clauses, never changes verdict semantics.

Kill switch: ``MYTHRIL_TPU_FRONTIER=0`` restores the exact prior
dense round kernels (callers stop passing frontier inputs, the ladder
runs :func:`ops.batched_sat.make_round_step` verbatim).  Knobs:
``MYTHRIL_TPU_FRONTIER_PERIOD`` (full-sweep safety-net period,
default 8), ``MYTHRIL_TPU_FRONTIER_FAN`` (queue vars processed per
gather step, default 16), ``MYTHRIL_TPU_FRONTIER_DEG`` (adjacency
rows kept per variable, default 32).
"""

import logging
import os
from typing import List, Optional, Sequence

import numpy as np

log = logging.getLogger(__name__)

#: frontier-gather iterations are much cheaper than full sweeps but
#: advance at most ``fan`` queue vars each, so a round's iteration
#: budget is the sweep budget times this (wide ripple fronts drain
#: over several gather steps where one dense sweep assigned them all)
FRONTIER_BUDGET_MULT = 4
#: bounded per-lane learned-clause buffer per round (host-harvested
#: and reset between rounds)
LEARN_CAP = 8
#: resolution-step bound for the in-kernel first-UIP walk; a conflict
#: whose current-level implication chain is longer simply learns
#: nothing (learning is an optimization, never load-bearing)
UIP_ITERS = 48
DEFAULT_PERIOD = 8
DEFAULT_FAN = 16
DEFAULT_DEG = 32


def frontier_enabled() -> bool:
    """``MYTHRIL_TPU_FRONTIER=0`` disables the event-driven tier: the
    round ladders run the exact prior dense kernels (A/B ablation and
    the findings-parity pin both ways)."""
    return os.environ.get("MYTHRIL_TPU_FRONTIER", "1").lower() not in (
        "0", "off", "false",
    )


def _env_int(name: str, default: int, floor: int = 1) -> int:
    from mythril_tpu.support.env import env_int

    return env_int(name, default, floor=floor)


def _tuned_int(name: str, knob: str, default: int,
               floor: int = 1) -> int:
    """Env pin wins; otherwise the autopilot tuner may publish a
    bounded override (autopilot/tuner.py); otherwise the default."""
    if not os.environ.get(name, "").strip():
        from mythril_tpu.autopilot import knob_override

        tuned = knob_override(knob)
        if tuned is not None:
            return max(floor, tuned)
    return _env_int(name, default, floor=floor)


def frontier_period() -> int:
    return _tuned_int("MYTHRIL_TPU_FRONTIER_PERIOD", "frontier_period",
                      DEFAULT_PERIOD)


def frontier_fan() -> int:
    return _tuned_int("MYTHRIL_TPU_FRONTIER_FAN", "frontier_fan",
                      DEFAULT_FAN)


def frontier_deg() -> int:
    return _env_int("MYTHRIL_TPU_FRONTIER_DEG", DEFAULT_DEG, floor=2)


# ---------------------------------------------------------------------------
# adjacency index (host build; device upload at the call sites)
# ---------------------------------------------------------------------------


def build_adjacency(rows: np.ndarray, v1: int,
                    deg: Optional[int] = None) -> np.ndarray:
    """Literal→clause-row adjacency over dense clause rows.

    ``rows [C, K]`` int32 (signed literals, 0 = pad).  Returns
    ``adj [v1, deg]`` int32: for variable ``v``, the row indices in
    which ``v`` occurs (either polarity), padded with -1.  Degrees past
    the cap are truncated — sound, because every kernel consumer runs
    periodic full sweeps that see the whole pool (a truncated list
    delays a unit, it cannot hide a verdict)."""
    if deg is None:
        deg = frontier_deg()
    adj = np.full((v1, deg), -1, dtype=np.int32)
    if rows.size == 0:
        return adj
    rid, kpos = np.nonzero(rows)
    if rid.size == 0:
        return adj
    var = np.abs(rows[rid, kpos]).astype(np.int64)
    keep = var < v1
    rid, var = rid[keep], var[keep]
    # unique (var, row) pairs in (var, row)-sorted order so each var's
    # slice lists its rows ascending and duplicates collapse
    key = var * np.int64(rows.shape[0] + 1) + rid
    ukey = np.unique(key)
    uvar = (ukey // np.int64(rows.shape[0] + 1)).astype(np.int64)
    urow = (ukey % np.int64(rows.shape[0] + 1)).astype(np.int32)
    # position of each pair within its var group
    first = np.searchsorted(uvar, uvar)
    slot = np.arange(len(uvar)) - first
    keep = slot < deg
    adj[uvar[keep], slot[keep]] = urow[keep]
    return adj


class LitAdjacency:
    """Host-side CSR adjacency over (row, literal) coordinates — the
    shared index behind the Pallas union layout's hot-tier growth
    (rows adjacent to a trail column in O(Σ deg) instead of an
    O(nnz) ``isin`` scan per round)."""

    def __init__(self, urow: np.ndarray, ulit: np.ndarray, n_rows: int):
        var = np.abs(ulit.astype(np.int64))
        order = np.argsort(var, kind="stable")
        self._rows = urow[order].astype(np.int64)
        svar = var[order]
        self.v1 = int(svar.max()) + 1 if svar.size else 1
        self._indptr = np.searchsorted(
            svar, np.arange(self.v1 + 1, dtype=np.int64)
        )
        self.n_rows = n_rows

    def rows_for_vars(self, cols: np.ndarray) -> np.ndarray:
        """Unique row ids (original-layout space) adjacent to any of
        ``cols``."""
        cols = np.asarray(cols, np.int64)
        cols = cols[(cols > 0) & (cols < self.v1)]
        if cols.size == 0:
            return np.empty(0, np.int64)
        starts = self._indptr[cols]
        stops = self._indptr[cols + 1]
        counts = stops - starts
        total = int(counts.sum())
        if total == 0:
            return np.empty(0, np.int64)
        # vectorized multi-slice gather
        out = np.repeat(starts - np.concatenate([[0], np.cumsum(counts)[:-1]]),
                        counts) + np.arange(total)
        return np.unique(self._rows[out])


# ---------------------------------------------------------------------------
# the frontier round kernel
# ---------------------------------------------------------------------------

#: field order of the resumable frontier solver state; the round
#: ladder re-packs survivors along axis 0 of every entry, so the
#: recent-queue, trail and learned buffers ride bucket compaction
FRONTIER_STATE_FIELDS = (
    "assign", "lvl", "reason", "tpos", "dvar", "dphase", "dflip",
    "depth", "status", "stamp", "recent", "cspos", "csneg",
    "fullsw", "fsteps", "nlearn", "learned", "pref",
)


def frontier_state0(assign: np.ndarray, n_real: int, max_decisions: int,
                    learn_cap: int = LEARN_CAP, width: int = 8,
                    pref_row=None) -> dict:
    """Host-side zero state for a frontier ladder over the
    assumption-seeded ``assign [B, V1]`` (int8); rows past ``n_real``
    are bucket padding, retired from step 0.  Seed assignments
    (assumptions, preassigned padding vars) live at level 0 with no
    reason and stamp 0, so they are never resolution pivots and appear
    in learned clauses as plain literals."""
    B, V1 = assign.shape
    D = max(1, min(max_decisions, V1))
    state = {
        "assign": assign.astype(np.int8, copy=True),
        "lvl": np.zeros((B, V1), np.int32),
        "reason": np.full((B, V1), -1, np.int32),
        "tpos": np.zeros((B, V1), np.int32),
        "dvar": np.zeros((B, D), np.int32),
        "dphase": np.zeros((B, D), np.int8),
        "dflip": np.zeros((B, D), bool),
        "depth": np.zeros(B, np.int32),
        "status": np.zeros(B, np.int32),
        "stamp": np.zeros(B, np.int32),
        "recent": np.zeros((B, V1), bool),
        # cached DLIS scores from the last full sweep: queue-drained
        # lanes decide on them between full views (single-var
        # decisions only — any free var is a sound decision, staleness
        # is pure heuristic drift)
        "cspos": np.zeros((B, V1), np.int32),
        "csneg": np.zeros((B, V1), np.int32),
        "fullsw": np.zeros(B, np.int32),
        "fsteps": np.zeros(B, np.int32),
        "nlearn": np.zeros(B, np.int32),
        "learned": np.zeros((B, learn_cap, width), np.int32),
        "pref": np.zeros((B, V1), np.int8),
    }
    if pref_row is not None:
        state["pref"][:] = np.asarray(pref_row, np.int8)
    state["status"][n_real:] = 3
    return state


def make_scan_rows(V1: int):
    """Build the shared BCP row-scan used by BOTH event-driven kernels
    (the per-round frontier ladder below and the persistent resident
    kernel in ops/resident.py) — one implementation so their unit/
    conflict semantics can never drift apart."""
    from mythril_tpu.ops.batched_sat import _require_jax

    _, jnp = _require_jax()
    from jax import lax

    def scan_rows(rows, row_ids, valid, assign, scores: bool):
        """One BCP evaluation over gathered clause rows.

        rows [B,G,K] signed literals (0 pad), row_ids [B,G] global row
        indices, valid [B,G].  Returns forced votes + per-polarity
        reason rows (+1-offset row ids), conflict flag + conflicting
        row, and (full view only) open-clause decision scores."""
        B, G, K = rows.shape
        var_idx = jnp.abs(rows)
        flat_var = var_idx.reshape(B, G * K)
        vals = jnp.sign(rows) * jnp.take_along_axis(
            assign.astype(jnp.int32), flat_var, axis=1
        ).reshape(B, G, K)
        is_real = (rows != 0) & valid[:, :, None]
        real_row = jnp.any(is_real, axis=2)
        sat = jnp.any((vals > 0) & is_real, axis=2)
        unknown_here = (vals == 0) & is_real
        num_unknown = jnp.sum(unknown_here.astype(jnp.int32), axis=2)
        all_false = jnp.all((vals < 0) | ~is_real, axis=2) & real_row
        unsat_yet = (~sat) & real_row
        unit = unsat_yet & (num_unknown == 1)
        forced_lit = jnp.sum(
            jnp.where(unit[:, :, None] & unknown_here, rows, 0), axis=2
        )  # [B, G]
        bg = lax.broadcasted_iota(jnp.int32, (B, G), 0)
        pos_var = jnp.where(forced_lit > 0, forced_lit, 0)
        neg_var = jnp.where(forced_lit < 0, -forced_lit, 0)
        zeros = jnp.zeros((B, V1), jnp.int32)
        fpos = zeros.at[bg, pos_var].max(
            jnp.where(forced_lit > 0, 1, 0)
        )
        fneg = zeros.at[bg, neg_var].max(
            jnp.where(forced_lit < 0, 1, 0)
        )
        rpos = zeros.at[bg, pos_var].max(
            jnp.where(forced_lit > 0, row_ids + 1, 0)
        )
        rneg = zeros.at[bg, neg_var].max(
            jnp.where(forced_lit < 0, row_ids + 1, 0)
        )
        conflict = jnp.any(all_false, axis=1)
        conflict_row = jnp.max(
            jnp.where(all_false, row_ids + 1, 0), axis=1
        ) - 1  # -1 = none
        if scores:
            open_unknown = (
                unknown_here & (unsat_yet & (num_unknown > 1))[:, :, None]
            )
            bflat = lax.broadcasted_iota(jnp.int32, (B, G * K), 0)
            spos = zeros.at[bflat, flat_var].add(
                (open_unknown & (rows > 0)).reshape(B, G * K)
                .astype(jnp.int32)
            )
            sneg = zeros.at[bflat, flat_var].add(
                (open_unknown & (rows < 0)).reshape(B, G * K)
                .astype(jnp.int32)
            )
        else:
            spos = zeros
            sneg = zeros
        return fpos, fneg, rpos, rneg, conflict, conflict_row, spos, sneg

    return scan_rows


def build_frontier_rounds(num_vars: int, budget: int,
                          max_decisions: int, fan: int, period: int,
                          learn_cap: int = LEARN_CAP,
                          uip_iters: int = UIP_ITERS):
    """Jittable batched frontier round over the FRONTIER_STATE_FIELDS
    tuple: ``rounds(lits[C,K], adj[V1,deg], *state) -> state'``.

    Status is RAW (0 live, 1 SAT candidate, 2 sound UNSAT, 3
    retired-undecided); ``fullsw``/``fsteps`` count per-lane active
    full sweeps / frontier-gather steps this round, and ``learned`` /
    ``nlearn`` carry the round's first-UIP clauses for the host
    harvest.  The iteration budget is ``budget * FRONTIER_BUDGET_MULT``
    (gather steps advance at most ``fan`` queue vars each).

    The search rules match ops/batched_sat.build_round_lane — dynamic
    DLIS decisions with warm-start phase preference, don't-care
    cascade, chronological backtracking, exhaustion-UNSAT — so the
    verdicts agree with the dense kernel; only the sweep *schedule*
    and the learned-clause side channel differ.
    """
    from mythril_tpu.ops.batched_sat import _require_jax

    jax, jnp = _require_jax()
    from jax import lax

    V1 = num_vars + 1
    D = max(1, min(max_decisions, V1))
    fan = max(1, min(fan, V1))  # top_k cannot exceed the var axis
    iters = budget * FRONTIER_BUDGET_MULT
    scan_rows = make_scan_rows(V1)

    def frontier_rounds(lits, adj, assign0, lvl0, reason0, tpos0, dvar0,
                        dphase0, dflip0, depth0, status0, stamp0, recent0,
                        cspos0, csneg0, fullsw0, fsteps0, nlearn0, learned0,
                        pref0):
        B = assign0.shape[0]
        C, K = lits.shape
        deg = adj.shape[1]
        col = lax.broadcasted_iota(jnp.int32, (B, V1), 1)
        dcol = lax.broadcasted_iota(jnp.int32, (B, D), 1)
        b1 = jnp.arange(B)

        def full_scan(assign):
            rows = jnp.broadcast_to(lits[None], (B, C, K))
            row_ids = jnp.broadcast_to(
                jnp.arange(C, dtype=jnp.int32)[None], (B, C)
            )
            return scan_rows(rows, row_ids,
                             jnp.ones((B, C), bool), assign, True)

        def frontier_scan(assign, recent):
            # pop up to `fan` queued vars per lane (largest ids first —
            # order is irrelevant to correctness, overflow stays queued)
            pri = jnp.where(recent, col, 0)
            picked_ids, _ = lax.top_k(pri, fan)          # [B, fan]
            picked = picked_ids > 0
            bf = lax.broadcasted_iota(jnp.int32, (B, fan), 0)
            clear = jnp.zeros((B, V1), bool).at[bf, picked_ids].max(picked)
            recent1 = recent & ~clear
            rids = adj[picked_ids]                       # [B, fan, deg]
            valid = (rids >= 0) & picked[:, :, None]
            rids_flat = jnp.where(valid, rids, 0).reshape(B, fan * deg)
            valid_flat = valid.reshape(B, fan * deg)
            rows = lits[rids_flat] * valid_flat[:, :, None]
            return (scan_rows(rows, rids_flat, valid_flat, assign,
                              False), recent1)

        def maybe_learn(A, lvl, reason, tpos, depth, do_learn,
                        conflict_row, nlearn, learned):
            """First-UIP resolution for every conflicting lane (the
            whole block is skipped via a scalar cond when no lane
            conflicts this iteration)."""
            crow = lits[jnp.clip(conflict_row, 0, C - 1)]     # [B, K]
            bk = lax.broadcasted_iota(jnp.int32, (B, K), 0)
            marked0 = jnp.zeros((B, V1), bool).at[
                bk, jnp.abs(crow)
            ].max(crow != 0)
            marked0 = marked0.at[:, 0].set(False)

            def uip_body(_, carry):
                marked, ok = carry
                atlvl = marked & (lvl == depth[:, None]) & (A != 0)
                cnt = jnp.sum(atlvl.astype(jnp.int32), axis=1)
                need = ok & (cnt > 1)
                key = jnp.where(atlvl, tpos, -1)
                piv = jnp.argmax(key, axis=1).astype(jnp.int32)  # [B]
                r = reason[b1, piv]
                # a pivot without a reason (decision/assumption) would
                # make the resolution step undefined — drop the clause
                ok1 = jnp.where(need & (r < 0), False, ok)
                need = need & (r >= 0)
                prow = lits[jnp.clip(r, 0, C - 1)]               # [B, K]
                add = jnp.zeros((B, V1), bool).at[
                    bk, jnp.abs(prow)
                ].max((prow != 0) & need[:, None])
                m1 = (marked | add) & ~(need[:, None] & (col == piv[:, None]))
                m1 = m1.at[:, 0].set(False)
                return jnp.where(need[:, None], m1, marked), ok1

            marked, ok = lax.fori_loop(
                0, uip_iters, uip_body, (marked0, do_learn)
            )
            atlvl = marked & (lvl == depth[:, None])
            ok = ok & (jnp.sum(atlvl.astype(jnp.int32), axis=1) <= 1)
            total = jnp.sum(marked.astype(jnp.int32), axis=1)
            ok = ok & (total >= 1) & (total <= K) & (nlearn < learn_cap)
            ids = jnp.where(marked, col, 0)
            kk = min(K, V1)
            vsel, _ = lax.top_k(ids, kk)                         # [B, kk]
            sgn = jnp.take_along_axis(
                A.astype(jnp.int32), jnp.clip(vsel, 0, V1 - 1), axis=1
            )
            litrow = jnp.zeros((B, K), jnp.int32).at[:, :kk].set(
                jnp.where(vsel > 0, -sgn * vsel, 0)
            )
            slot = jnp.clip(nlearn, 0, learn_cap - 1)
            old = learned[b1, slot]
            learned1 = learned.at[b1, slot].set(
                jnp.where(ok[:, None], litrow, old)
            )
            return learned1, nlearn + ok.astype(jnp.int32)

        def body(carry):
            (A, lvl, reason, tpos, dvar, dphase, dflip, depth, status,
             stamp, recent, cspos, csneg, fullsw, fsteps, nlearn,
             learned, it) = carry
            active = status == 0                                 # [B]
            # full view: periodic safety net, or every live queue
            # drained (a decision / SAT completion needs exact scores
            # and the whole-pool conflict check)
            queued = jnp.any(recent & active[:, None])
            do_full = ((it % period) == 0) | ~queued
            (fpos, fneg, rpos, rneg, conflict, conflict_row, spos,
             sneg), recent1 = lax.cond(
                do_full,
                lambda a, r: (full_scan(a), jnp.zeros_like(r)),
                frontier_scan,
                A, recent,
            )
            full_b = jnp.broadcast_to(do_full, (B,))
            free = (A == 0) & (col > 1)  # col 1 = constant-TRUE anchor
            force_pos = (fpos > 0) & free
            force_neg = (fneg > 0) & free
            forced = force_pos | force_neg
            has_force = jnp.any(forced, axis=1)
            open_any = jnp.any(free, axis=1)
            # contradictory forcings are NOT flagged here: the kernel
            # assigns the positive phase and the opposing unit row —
            # adjacent to the var, hence rescanned — turns all-false
            # next iteration, yielding a conflict with a real row the
            # first-UIP walk can start from
            nstamp = stamp + active.astype(jnp.int32)

            # --- conflict: learn, then chronological backtrack
            held = dcol < depth[:, None]
            unflipped = held & ~dflip
            Lm = jnp.max(jnp.where(unflipped, dcol + 1, 0), axis=1)
            unsat_now = active & conflict & (Lm == 0)
            do_bt = active & conflict & (Lm > 0)
            do_learn = do_bt & (conflict_row >= 0) & (depth > 0)
            learned1, nlearn1 = lax.cond(
                jnp.any(do_learn),
                maybe_learn,
                lambda A_, l_, r_, t_, d_, dl_, cr_, nl_, le_: (le_, nl_),
                A, lvl, reason, tpos, depth, do_learn, conflict_row,
                nlearn, learned,
            )
            bslot = jnp.maximum(Lm - 1, 0)
            bvar = dvar[b1, bslot]                               # [B]
            bphase = (-dphase[b1, bslot]).astype(jnp.int8)
            popped_assign = do_bt[:, None] & (A != 0) & (lvl >= Lm[:, None])
            at_bvar = do_bt[:, None] & (col == bvar[:, None])
            A1 = jnp.where(popped_assign, 0, A).astype(jnp.int8)
            A1 = jnp.where(at_bvar, bphase[:, None], A1).astype(jnp.int8)
            lvl1 = jnp.where(at_bvar, Lm[:, None], lvl)
            reason1 = jnp.where(at_bvar, -1, reason)
            tpos1 = jnp.where(at_bvar, nstamp[:, None], tpos)
            popped = do_bt[:, None] & (dcol >= Lm[:, None])
            at_b = do_bt[:, None] & (dcol == bslot[:, None])
            dvar1 = jnp.where(popped, 0, dvar)
            dphase1 = jnp.where(
                popped, 0, jnp.where(at_b, bphase[:, None], dphase)
            ).astype(jnp.int8)
            dflip1 = jnp.where(popped, False, jnp.where(at_b, True, dflip))
            depth1 = jnp.where(do_bt, Lm, depth)
            recent2 = (recent1 & ~popped_assign) | at_bvar

            # --- quiet + forced: assign all forced literals, record
            # the forcing row as each var's reason, stamp the trail
            do_force = active & ~conflict & has_force
            assigned_now = do_force[:, None] & forced
            delta = jnp.where(force_pos, 1, -1).astype(jnp.int8)
            A2 = jnp.where(assigned_now, delta, A1).astype(jnp.int8)
            lvl2 = jnp.where(assigned_now, depth[:, None], lvl1)
            reason2 = jnp.where(
                assigned_now, jnp.where(force_pos, rpos, rneg) - 1, reason1
            )
            tpos2 = jnp.where(assigned_now, nstamp[:, None], tpos1)
            recent3 = recent2 | assigned_now

            # --- quiet + open: decide (dynamic DLIS + warm-start
            # phase preference, same rules as build_round_lane).  A
            # full view decides on fresh scores and refreshes the
            # cache; a queue-drained lane on a gather view decides on
            # the CACHED scores from its last full sweep — any free
            # var is a sound single-var decision, staleness is pure
            # heuristic drift — so decisions stop forcing a full
            # sweep each.  The don't-care cascade stays full-view
            # gated: its "provably in no open clause" argument (which
            # keeps exhaustion a refutation without stack entries)
            # needs exact scores.
            qempty = ~jnp.any(recent1, axis=1)
            want = active & ~conflict & ~has_force & open_any & (
                full_b | qempty
            )
            can = depth1 < D
            do_dec = want & can
            bail = want & ~can
            spos_eff = jnp.where(do_full, spos, cspos)
            sneg_eff = jnp.where(do_full, sneg, csneg)
            score = jnp.where(free & ~forced, spos_eff + sneg_eff + 1, -1)
            var = jnp.argmax(score, axis=1).astype(jnp.int32)    # [B]
            dlis = jnp.where(
                spos_eff[b1, var] >= sneg_eff[b1, var], 1, -1
            ).astype(jnp.int8)
            prefv = pref0[b1, var]
            phase = jnp.where(prefv != 0, prefv, dlis).astype(jnp.int8)
            ndepth = depth1 + 1
            dontcare = (
                free & ~forced & (spos + sneg == 0) & full_b[:, None]
            )
            at_var = col == var[:, None]
            newly = do_dec[:, None] & (dontcare | at_var)
            A3 = jnp.where(
                newly,
                jnp.where(at_var, phase[:, None], jnp.int8(1)),
                A2,
            ).astype(jnp.int8)
            lvl3 = jnp.where(newly, ndepth[:, None], lvl2)
            reason3 = jnp.where(newly, -1, reason2)
            tpos3 = jnp.where(newly, nstamp[:, None], tpos2)
            recent4 = recent3 | (do_dec[:, None] & at_var)
            at_new = do_dec[:, None] & (dcol == depth1[:, None])
            dvar2 = jnp.where(at_new, var[:, None], dvar1)
            dphase2 = jnp.where(at_new, phase[:, None], dphase1).astype(
                jnp.int8
            )
            dflip2 = jnp.where(at_new, False, dflip1)
            depth2 = jnp.where(do_dec, ndepth, depth1)

            # --- quiet + complete on a full view: SAT candidate
            done_sat = (
                active & ~conflict & ~has_force & ~open_any & full_b
            )
            status1 = jnp.where(unsat_now, 2, status)
            status1 = jnp.where(done_sat, 1, status1)
            status1 = jnp.where(bail, 3, status1)
            fullsw1 = fullsw + (active & full_b).astype(jnp.int32)
            fsteps1 = fsteps + (active & ~full_b).astype(jnp.int32)
            return (A3, lvl3, reason3, tpos3, dvar2, dphase2, dflip2,
                    depth2, status1, nstamp, recent4, spos_eff,
                    sneg_eff, fullsw1, fsteps1, nlearn1, learned1,
                    it + 1)

        def cond(carry):
            return jnp.any(carry[8] == 0) & (carry[-1] < iters)

        init = (assign0, lvl0, reason0, tpos0, dvar0, dphase0, dflip0,
                depth0, status0, stamp0, recent0, cspos0, csneg0,
                fullsw0, fsteps0, nlearn0, learned0, jnp.int32(0))
        out = lax.while_loop(cond, body, init)
        return out[:-1] + (pref0,)

    return frontier_rounds


def make_frontier_round_step(num_vars: int, budget: int,
                             max_decisions: int):
    """Jitted frontier round for the gather ladder (cache-keyed by the
    callers together with the fan/period knobs):
    ``fn(lits[C,K], adj[V1,deg], *state) -> state'`` over
    FRONTIER_STATE_FIELDS."""
    from mythril_tpu.ops.batched_sat import _require_jax

    jax, _ = _require_jax()
    return jax.jit(build_frontier_rounds(
        num_vars, budget, max_decisions,
        fan=frontier_fan(), period=frontier_period(),
    ))


# ---------------------------------------------------------------------------
# host harvest: device-learned clauses -> the blast context's pool
# ---------------------------------------------------------------------------


def harvest_learned(ctx, clause_rows: Sequence[np.ndarray],
                    col_to_var: Optional[np.ndarray] = None) -> int:
    """Feed first-UIP clauses emitted by the frontier kernel into the
    blast context's nogood channel.  ``clause_rows`` are padded int32
    literal rows in kernel column space; ``col_to_var`` maps column ids
    back to pool variable ids (None = identity, the full-pool gather
    tier).  Dedupes within the batch; the native side dedupes globally,
    rejects tautologies and enforces the width cap.  Returns how many
    clauses the pool accepted (``learned_clauses`` telemetry)."""
    seen = set()
    accepted = 0
    for row in clause_rows:
        lits: List[int] = []
        ok = True
        for lit in row:
            lit = int(lit)
            if lit == 0:
                continue
            var = abs(lit)
            if col_to_var is not None:
                if var >= len(col_to_var):
                    ok = False
                    break
                var = int(col_to_var[var])
                if var <= 0:
                    ok = False
                    break
            lits.append(var if lit > 0 else -var)
        if not ok or not lits:
            continue
        key = tuple(sorted(lits))
        if key in seen:
            continue
        seen.add(key)
        accepted += ctx.harvest_device_clauses([lits])
    return accepted
