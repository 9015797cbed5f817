"""Resident solver: ONE persistent kernel for the whole round ladder.

The frontier tier (ops/frontier.py) cut *what* a sweep reads, but the
round ladder still exits to Python every budget rung — watchdog check,
lane retirement/re-pack, learned-clause harvest — thousands of tiny
dispatches per analysis, each paying a host<->device round trip.
SatIn (arxiv 2303.02588) and the FPGA BCP streamers (arxiv 2401.07429)
get their throughput from keeping the entire propagate->decide->learn
loop resident in hardware.  This module is that design for XLA: one
``lax.while_loop`` dispatch that runs until a terminal condition the
DEVICE decides, built from the same pieces as the frontier round —
:func:`ops.frontier.make_scan_rows` is shared verbatim for frontier
steps and the extra pool, and the full sweep (:func:`make_count_scan`)
returns its outputs exactly, so the BCP/conflict semantics of the two
kernels can never drift.

What moves into the kernel:

- **The whole ladder loop.**  No per-round budget rungs: the loop runs
  to ``MYTHRIL_TPU_RESIDENT_BUDGET`` total iterations (default: the
  ladder's GATHER_STEPS x FRONTIER_BUDGET_MULT, so the search effort
  matches the multi-dispatch ladder it replaces).
- **Mid-dispatch learned-clause sharing** (the remaining half of
  PR 8): first-UIP clauses land in a *shared* append-only row pool
  ``extra [E+1, K]`` carried through the loop.  Every scan — full
  sweeps AND frontier gathers — also scans the extra block, so a
  clause one lane learns prunes its *siblings in the same dispatch*
  instead of waiting for the next dispatch's delta upload.  Appends
  dedupe against the pool and within the batch (the first-UIP rows
  come out of ``top_k`` in canonical var-descending order, so equal
  clauses are equal rows); row ``E`` is a masked-write sink, never
  scanned.  Extra rows are derived by resolution over pool rows, so
  they are implied by the pool and valid for every lane — exactly the
  argument that lets the host harvest them afterwards.
- **Lane retirement / repack, mask-level.**  XLA shapes are static, so
  a decided lane cannot shrink the batch mid-dispatch; instead every
  per-lane mask already keyed on ``status == 0`` stops charging it
  work (``fullsw``/``fsteps`` count only active lanes, preserving the
  sweep-utilization telemetry), and the loop exits the moment no lane
  is live — the all-decided exit replaces the host's retire+repack.
- **A device-side watchdog**: ``stall`` counts consecutive iterations
  in which NO lane advanced (no forcing, backtrack, decision, or
  status change anywhere in the batch).  Healthy search bounds such
  stretches by the queue-drain length (~V1/fan) plus the full-sweep
  period; ``stall >= MYTHRIL_TPU_RESIDENT_WATCHDOG`` trips the loop
  back to the host, which retires survivors undecided.  The host-side
  EWMA watchdog stays armed around the dispatch (key family
  ``resident:{lane bucket}``) as the outer line of defense.

Exit taxonomy (host-derived from the returned state, see
:func:`exit_reason`): ``all_decided`` (no live lane remains — the
only exit on healthy inputs), ``budget`` (iteration budget exhausted;
survivors fall to the CDCL tail exactly like a ladder bail) and
``watchdog`` (device-side stall trip; survivors likewise undecided).
All three are sound: verdict-bearing statuses are only ever written by
the same rules as the frontier kernel.

Soundness of the extra pool: a conflict found in an extra row is a
conflict of an implied clause, so backtracking/UNSAT on it is sound;
forced literals recorded with an extra-row reason resolve through
:func:`maybe_learn`'s row fetch, which reads pool and extra rows
uniformly.  The don't-care cascade keeps its "provably in no open
clause" argument by additionally excluding any variable that occurs in
the extra pool at all (an implied clause CAN falsify a cascade-
assigned var, which would unsoundly prune the sibling phase — so such
vars are simply never cascade-assigned).

Kill switch: ``MYTHRIL_TPU_RESIDENT_KERNEL=0`` restores the exact
multi-dispatch round ladders (and the resident path requires the
frontier tier — ``MYTHRIL_TPU_FRONTIER=0`` disables both).  Knobs
(all registered with support/env.py so ``validate_env`` rejects typos
at startup): ``MYTHRIL_TPU_RESIDENT_BUDGET`` (total in-kernel
iterations), ``MYTHRIL_TPU_RESIDENT_WATCHDOG`` (stall-trip counter),
``MYTHRIL_TPU_RESIDENT_EXTRA`` (shared learned-row pool cap).
"""

import numpy as np

from mythril_tpu.ops.frontier import (
    FRONTIER_STATE_FIELDS, LEARN_CAP, UIP_ITERS, FRONTIER_BUDGET_MULT,
    frontier_enabled, frontier_fan, frontier_period, frontier_state0,
    make_scan_rows,
)
from mythril_tpu.support.env import env_flag, env_int

#: per-lane solver state — identical layout to the frontier ladder
#: (satellite: BOTH ladders enter the resident kernel through this one
#: state layout), so retry/bisect slicing along axis 0 stays valid
RESIDENT_LANE_FIELDS = FRONTIER_STATE_FIELDS
#: batch-shared state: the mid-dispatch learned-row pool and the
#: device-side watchdog/budget counters.  NOT lane-sliceable — the
#: dispatch supervisor re-seeds them fresh (zeros) on every attempt,
#: including bisection halves (learned rows are an optimization, and
#: an empty pool is always a sound start)
RESIDENT_SHARED_FIELDS = ("extra", "nextra", "stall", "itc")
RESIDENT_STATE_FIELDS = RESIDENT_LANE_FIELDS + RESIDENT_SHARED_FIELDS

DEFAULT_WATCHDOG = 2048  # > worst healthy no-progress stretch
                         # (queue drain ~V1/fan <= 512 at the caps)
DEFAULT_EXTRA = 64       # shared learned-row pool cap


def resident_kernel_enabled() -> bool:
    """``MYTHRIL_TPU_RESIDENT_KERNEL=0`` restores the exact
    multi-dispatch round ladders (A/B ablation + parity pin both
    ways).  The resident kernel is built from the frontier state
    layout, so the frontier kill switch disables it too."""
    return env_flag("MYTHRIL_TPU_RESIDENT_KERNEL", True) and (
        frontier_enabled()
    )


def resident_budget() -> int:
    """Total in-kernel iterations for one resident dispatch.  Default
    matches the multi-dispatch ladder's total effort (GATHER_STEPS
    sweep budget x FRONTIER_BUDGET_MULT gather amplification)."""
    from mythril_tpu.ops.batched_sat import GATHER_STEPS

    return env_int("MYTHRIL_TPU_RESIDENT_BUDGET",
                   GATHER_STEPS * FRONTIER_BUDGET_MULT, floor=1)


def resident_watchdog_limit() -> int:
    """Device-side stall trip: consecutive no-progress iterations
    before the kernel exits back to the host."""
    return env_int("MYTHRIL_TPU_RESIDENT_WATCHDOG", DEFAULT_WATCHDOG,
                   floor=1)


def resident_extra_cap() -> int:
    """Rows in the shared mid-dispatch learned-clause pool (appends
    past the cap are dropped — learning is never load-bearing)."""
    return env_int("MYTHRIL_TPU_RESIDENT_EXTRA", DEFAULT_EXTRA, floor=1)


def subset_matrix(id_sets):
    """Pairwise subset test over lanes' constraint-id sets, packed as
    uint64 bitset rows — the veritesting tier's frontier-subsumption
    sweep (laser/ethereum/veritest.py) asks "whose constraint set
    contains whose?" for every lane pair at one site in one batched
    pass, the same mask-level lane model the resident kernel retires
    lanes with.  Returns bool[N, N] where ``out[x, y]`` means
    ``id_sets[y] <= id_sets[x]`` (lane x is at least as constrained
    as lane y).  Diagonal is True."""
    n = len(id_sets)
    universe = sorted(set().union(*id_sets)) if id_sets else []
    if not universe:
        return np.ones((n, n), dtype=bool)
    position = {nid: i for i, nid in enumerate(universe)}
    words = (len(universe) + 63) // 64
    rows = np.zeros((n, words), dtype=np.uint64)
    for lane, ids in enumerate(id_sets):
        for nid in ids:
            bit = position[nid]
            rows[lane, bit >> 6] |= np.uint64(1 << (bit & 63))
    # out[x, y]: every bit of y present in x  <=>  y & ~x == 0
    return ~np.any(rows[None, :, :] & ~rows[:, None, :], axis=-1)


def resident_shared0(extra_cap: int, width: int) -> dict:
    """Zero shared state for one resident dispatch: empty extra pool
    (row ``extra_cap`` is the masked-write sink), counters at zero."""
    return {
        "extra": np.zeros((extra_cap + 1, width), np.int32),
        "nextra": np.zeros(1, np.int32),
        "stall": np.zeros(1, np.int32),
        "itc": np.zeros(1, np.int32),
    }


def resident_state0(assign: np.ndarray, n_real: int, max_decisions: int,
                    learn_cap: int = LEARN_CAP, width: int = 8,
                    pref_row=None, extra_cap=None) -> dict:
    """Host-side zero state over RESIDENT_STATE_FIELDS: the frontier
    lane state plus the shared extra pool / counters."""
    if extra_cap is None:
        extra_cap = resident_extra_cap()
    state = frontier_state0(assign, n_real, max_decisions,
                            learn_cap=learn_cap, width=width,
                            pref_row=pref_row)
    state.update(resident_shared0(extra_cap, width))
    return state


def exit_reason(status: np.ndarray, stall: int, itc: int,
                watchdog: int, budget: int) -> str:
    """Name why a resident dispatch returned (profile_t3 taxonomy):
    ``all_decided`` | ``watchdog`` | ``budget``.  Bucket-pad lanes
    enter retired (status 3), so "no zeros left" is exactly the
    kernel's own all-decided exit condition."""
    if not np.any(np.asarray(status) == 0):
        return "all_decided"
    if stall >= watchdog:
        return "watchdog"
    return "budget"


def make_count_scan(V1: int):
    """The resident kernel's full sweep as occurrence-count matmuls:
    ``(count_lits, count_scan)``.

    Within one dispatch the clause rows never change, so
    ``count_lits(lits[C, K])`` builds once, outside the loop,
    ``occ int8[C, 2*V1]`` (column ``v`` counts the occurrences of
    ``+v`` in a row, column ``V1 + v`` those of ``-v``) and
    ``real[C]`` (rows holding a literal).  Counts, not incidence, so a
    row with a duplicate literal or with both ``v`` and ``-v`` keeps
    the unknown count a per-literal scan gives it.

    ``count_scan((occ, real), assign[B, V1])`` then returns exactly
    what :func:`ops.frontier.make_scan_rows`' scan returns over every
    row with ``scores=True``, from two int8 MXU matmuls and one B·C
    scatter (the reason rows) in place of a B·C·K gather and six
    scatters.  Per row it counts the true, the unknown and the unknown
    positive occurrences, and sums the unknown occurrences' var ids in
    7-bit digits (an int8 operand holds each digit exactly): in a unit
    row that sum is the one unknown var.  The DLIS scores project the
    open rows back onto their literals, masked to unknown vars.
    Memory: ``C * 2 * V1`` bytes, 268 MB at the cone caps.
    """
    from mythril_tpu.ops.batched_sat import _require_jax

    _, jnp = _require_jax()
    from jax import lax

    digit_weights = [1]
    while digit_weights[-1] * 128 < V1:
        digit_weights.append(digit_weights[-1] * 128)

    def count_lits(lits):
        C, K = lits.shape
        lit_col = jnp.where(lits < 0, V1 - lits, lits)
        row = lax.broadcasted_iota(jnp.int32, (C, K), 0)
        real_lit = lits != 0
        occ = jnp.zeros((C, 2 * V1), jnp.int8).at[row, lit_col].add(
            real_lit.astype(jnp.int8)
        )
        return occ, jnp.any(real_lit, axis=1)

    def count_scan(counted, assign):
        occ, real = counted
        B = assign.shape[0]
        C = occ.shape[0]
        true, false, unk = assign == 1, assign == -1, assign == 0
        var = lax.broadcasted_iota(jnp.int32, (B, V1), 1)

        def lit_cols(pos, neg):
            return jnp.concatenate([pos, neg], axis=1).astype(jnp.int8)

        lhs = [lit_cols(true, false), lit_cols(unk, unk),
               lit_cols(unk, jnp.zeros_like(unk))]
        for w in digit_weights:
            digit = jnp.where(unk, (var // w) % 128, 0)
            lhs.append(lit_cols(digit, digit))
        per_row = lax.dot_general(
            jnp.concatenate(lhs, axis=0), occ, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).reshape(len(lhs), B, C)
        n_true, n_unknown, n_unknown_pos = per_row[0], per_row[1], per_row[2]
        unknown_var = sum(per_row[3 + i] * w
                          for i, w in enumerate(digit_weights))
        unsat_yet = (n_true == 0) & real[None, :]
        all_false = unsat_yet & (n_unknown == 0)
        unit = unsat_yet & (n_unknown == 1)
        row_id1 = lax.broadcasted_iota(jnp.int32, (B, C), 1) + 1
        forced_col = jnp.where(
            unit,
            jnp.where(n_unknown_pos == 1, unknown_var, V1 + unknown_var),
            0,
        )
        bc = lax.broadcasted_iota(jnp.int32, (B, C), 0)
        reasons = jnp.zeros((B, 2 * V1), jnp.int32).at[bc, forced_col].max(
            jnp.where(unit, row_id1, 0)
        )
        rpos, rneg = reasons[:, :V1], reasons[:, V1:]
        conflict = jnp.any(all_false, axis=1)
        conflict_row = jnp.max(jnp.where(all_false, row_id1, 0), axis=1) - 1
        is_open = unsat_yet & (n_unknown > 1)
        score = lax.dot_general(
            is_open.astype(jnp.int8), occ, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return ((rpos > 0).astype(jnp.int32), (rneg > 0).astype(jnp.int32),
                rpos, rneg, conflict, conflict_row,
                jnp.where(unk, score[:, :V1], 0),
                jnp.where(unk, score[:, V1:], 0))

    return count_lits, count_scan


def build_resident_rounds(num_vars: int, budget: int,
                          max_decisions: int, fan: int, period: int,
                          watchdog: int, extra_cap: int,
                          learn_cap: int = LEARN_CAP,
                          uip_iters: int = UIP_ITERS):
    """Jittable persistent solve over RESIDENT_STATE_FIELDS:
    ``rounds(lits[C,K], adj[V1,deg], *state) -> state'``.

    The search rules are the frontier kernel's (dynamic DLIS with
    warm-start phase preference, adjacency-gather BCP between periodic
    full sweeps, chronological backtracking, in-kernel first-UIP
    learning) — the differences are purely structural: the loop runs
    the WHOLE budget in one dispatch, learned rows append to the
    shared ``extra`` pool mid-dispatch and are scanned by every lane
    from the next iteration on, and the loop condition adds the
    device-side stall watchdog.  Status is RAW (0 live, 1 SAT
    candidate, 2 sound UNSAT, 3 retired-undecided); the supervisor
    maps 3 -> 0 on return like the ladder does.
    """
    from mythril_tpu.ops.batched_sat import _require_jax

    jax, jnp = _require_jax()
    from jax import lax

    V1 = num_vars + 1
    D = max(1, min(max_decisions, V1))
    fan = max(1, min(fan, V1))  # top_k cannot exceed the var axis
    E = extra_cap
    scan_rows = make_scan_rows(V1)
    count_lits, count_scan = make_count_scan(V1)

    # jitted as the XLA module jit_rounds; the frontier and dense round
    # kernels have names of their own, so jit_rounds in a profile is
    # this kernel alone
    def rounds(lits, adj, assign0, lvl0, reason0, tpos0, dvar0, dphase0,
               dflip0, depth0, status0, stamp0, recent0, cspos0,
               csneg0, fullsw0, fsteps0, nlearn0, learned0, pref0,
               extra0, nextra0, stall0, itc0):
        B = assign0.shape[0]
        C, K = lits.shape
        deg = adj.shape[1]
        col = lax.broadcasted_iota(jnp.int32, (B, V1), 1)
        dcol = lax.broadcasted_iota(jnp.int32, (B, D), 1)
        b1 = jnp.arange(B)
        erow = jnp.arange(E, dtype=jnp.int32)

        def extra_scan(assign, extra, nextra):
            """Scan the shared learned-row block (row ids offset by C
            so reasons/conflicts name extra rows unambiguously).  Rows
            past ``nextra`` are invalid; the sink row E is excluded by
            construction."""
            rows = jnp.broadcast_to(extra[None, :E], (B, E, K))
            row_ids = jnp.broadcast_to((C + erow)[None], (B, E))
            valid = jnp.broadcast_to((erow < nextra[0])[None], (B, E))
            return scan_rows(rows, row_ids, valid, assign, False)

        def merge(pool_res, ex_res):
            """Combine pool-scan and extra-scan votes.  Max over the
            +1-offset reason rows is sound (any real forcing row is a
            valid reason); scores stay pool-only (the extra scan never
            computes them — decision heuristics, not soundness)."""
            fp1, fn1, rp1, rn1, c1, cr1, sp1, sn1 = pool_res
            fp2, fn2, rp2, rn2, c2, cr2, _, _ = ex_res
            return (jnp.maximum(fp1, fp2), jnp.maximum(fn1, fn2),
                    jnp.maximum(rp1, rp2), jnp.maximum(rn1, rn2),
                    c1 | c2, jnp.maximum(cr1, cr2), sp1, sn1)

        # the pool rows' occurrence counts, once per dispatch: every
        # full sweep of the loop reads them (see make_count_scan)
        counted = count_lits(lits)

        def full_scan(assign, extra, nextra):
            return merge(count_scan(counted, assign),
                         extra_scan(assign, extra, nextra))

        def frontier_scan(assign, recent, extra, nextra):
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
            pool_res = scan_rows(rows, rids_flat, valid_flat, assign,
                                 False)
            # the adjacency index never covers extra rows, so the whole
            # extra block rides every gather step (E is small) — THE
            # property that makes mid-dispatch learning visible to
            # sibling lanes immediately instead of at the next full
            # sweep
            return (merge(pool_res, extra_scan(assign, extra, nextra)),
                    recent1)

        def fetch_rows(r, extra):
            """Clause row for id ``r`` — pool rows and extra rows read
            uniformly (reasons/conflicts may name either)."""
            from_pool = lits[jnp.clip(r, 0, C - 1)]
            from_extra = extra[jnp.clip(r - C, 0, E - 1)]
            return jnp.where((r >= C)[:, None], from_extra, from_pool)

        def maybe_learn(A, lvl, reason, tpos, depth, do_learn,
                        conflict_row, nlearn, learned, extra):
            """First-UIP resolution (frontier rules), with the row
            fetch extended over the extra pool — resolving against an
            implied clause preserves implication, so learned-from-
            learned rows are as valid as any.  Additionally returns
            the per-lane canonical clause row + emit flag so the
            caller can append to the shared pool."""
            crow = fetch_rows(conflict_row, extra)            # [B, K]
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
                ok1 = jnp.where(need & (r < 0), False, ok)
                need = need & (r >= 0)
                prow = fetch_rows(r, extra)                      # [B, K]
                add = jnp.zeros((B, V1), bool).at[
                    bk, jnp.abs(prow)
                ].max((prow != 0) & need[:, None])
                m1 = (marked | add) & ~(
                    need[:, None] & (col == piv[:, None])
                )
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
            return learned1, nlearn + ok.astype(jnp.int32), litrow, ok

        def append_extra(extra, nextra, litrow, okl):
            """Mid-dispatch append of this iteration's learned rows to
            the shared pool.  ``litrow`` rows are canonical (top_k var-
            descending), so duplicate clauses are duplicate rows: each
            lane dedupes against the live pool prefix and against
            earlier lanes of the same iteration.  Distinct survivors
            get consecutive slots via a cumsum offset; overflow and
            masked lanes write harmlessly to the sink row E."""
            ne = nextra[0]
            valid = erow < ne                                   # [E]
            dup = jnp.any(
                jnp.all(extra[None, :E] == litrow[:, None, :], axis=2)
                & valid[None, :], axis=1)                       # [B]
            same = jnp.all(
                litrow[:, None, :] == litrow[None, :, :], axis=2
            )
            earlier = jnp.any(
                jnp.tril(same, k=-1) & okl[None, :], axis=1
            )
            ok2 = okl & ~dup & ~earlier & jnp.any(litrow != 0, axis=1)
            okn = ok2.astype(jnp.int32)
            offs = ne + jnp.cumsum(okn) - okn                   # [B]
            live_write = ok2 & (offs < E)
            slot = jnp.where(live_write, offs, E)
            extra1 = extra.at[slot].set(
                jnp.where(live_write[:, None], litrow, extra[slot])
            )
            nextra1 = jnp.minimum(jnp.int32(E), ne + jnp.sum(okn))
            return extra1, jnp.reshape(nextra1, (1,))

        def body(carry):
            (A, lvl, reason, tpos, dvar, dphase, dflip, depth, status,
             stamp, recent, cspos, csneg, fullsw, fsteps, nlearn,
             learned, extra, nextra, stall, it) = carry
            active = status == 0                                 # [B]
            queued = jnp.any(recent & active[:, None])
            do_full = ((it % period) == 0) | ~queued
            (fpos, fneg, rpos, rneg, conflict, conflict_row, spos,
             sneg), recent1 = lax.cond(
                do_full,
                lambda a, r, e, ne: (full_scan(a, e, ne),
                                     jnp.zeros_like(r)),
                frontier_scan,
                A, recent, extra, nextra,
            )
            full_b = jnp.broadcast_to(do_full, (B,))
            free = (A == 0) & (col > 1)  # col 1 = constant-TRUE anchor
            force_pos = (fpos > 0) & free
            force_neg = (fneg > 0) & free
            forced = force_pos | force_neg
            has_force = jnp.any(forced, axis=1)
            open_any = jnp.any(free, axis=1)
            nstamp = stamp + active.astype(jnp.int32)

            # --- conflict: learn (+ shared append), then backtrack
            held = dcol < depth[:, None]
            unflipped = held & ~dflip
            Lm = jnp.max(jnp.where(unflipped, dcol + 1, 0), axis=1)
            unsat_now = active & conflict & (Lm == 0)
            do_bt = active & conflict & (Lm > 0)
            do_learn = do_bt & (conflict_row >= 0) & (depth > 0)
            zrow = jnp.zeros((B, K), jnp.int32)

            def learn_and_append(A_, lvl_, r_, t_, d_, dl_, cr_, nl_,
                                 le_, ex_, ne_):
                le1, nl1, litrow, okl = maybe_learn(
                    A_, lvl_, r_, t_, d_, dl_, cr_, nl_, le_, ex_
                )
                ex1, ne1 = append_extra(ex_, ne_, litrow, okl)
                return le1, nl1, ex1, ne1

            learned1, nlearn1, extra1, nextra1 = lax.cond(
                jnp.any(do_learn),
                learn_and_append,
                lambda A_, lvl_, r_, t_, d_, dl_, cr_, nl_, le_, ex_,
                ne_: (le_, nl_, ex_, ne_),
                A, lvl, reason, tpos, depth, do_learn, conflict_row,
                nlearn, learned, extra, nextra,
            )
            bslot = jnp.maximum(Lm - 1, 0)
            bvar = dvar[b1, bslot]                               # [B]
            bphase = (-dphase[b1, bslot]).astype(jnp.int8)
            popped_assign = (
                do_bt[:, None] & (A != 0) & (lvl >= Lm[:, None])
            )
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
            dflip1 = jnp.where(
                popped, False, jnp.where(at_b, True, dflip)
            )
            depth1 = jnp.where(do_bt, Lm, depth)
            recent2 = (recent1 & ~popped_assign) | at_bvar

            # --- quiet + forced
            do_force = active & ~conflict & has_force
            assigned_now = do_force[:, None] & forced
            delta = jnp.where(force_pos, 1, -1).astype(jnp.int8)
            A2 = jnp.where(assigned_now, delta, A1).astype(jnp.int8)
            lvl2 = jnp.where(assigned_now, depth[:, None], lvl1)
            reason2 = jnp.where(
                assigned_now, jnp.where(force_pos, rpos, rneg) - 1,
                reason1,
            )
            tpos2 = jnp.where(assigned_now, nstamp[:, None], tpos1)
            recent3 = recent2 | assigned_now

            # --- quiet + open: decide (frontier rules; the don't-care
            # cascade additionally excludes any var occurring in the
            # extra pool — an implied clause could falsify a cascade
            # assignment and unsoundly prune the sibling phase, so
            # those vars always go through real decisions)
            qempty = ~jnp.any(recent1, axis=1)
            want = active & ~conflict & ~has_force & open_any & (
                full_b | qempty
            )
            can = depth1 < D
            do_dec = want & can
            bail = want & ~can
            spos_eff = jnp.where(do_full, spos, cspos)
            sneg_eff = jnp.where(do_full, sneg, csneg)
            score = jnp.where(
                free & ~forced, spos_eff + sneg_eff + 1, -1
            )
            var = jnp.argmax(score, axis=1).astype(jnp.int32)    # [B]
            dlis = jnp.where(
                spos_eff[b1, var] >= sneg_eff[b1, var], 1, -1
            ).astype(jnp.int8)
            prefv = pref0[b1, var]
            phase = jnp.where(prefv != 0, prefv, dlis).astype(jnp.int8)
            ndepth = depth1 + 1
            ne1 = nextra1[0]
            in_extra = jnp.zeros((V1,), bool).at[
                jnp.abs(extra1[:E]).reshape(-1)
            ].max(
                ((erow < ne1)[:, None] & (extra1[:E] != 0)).reshape(-1)
            )
            in_extra = in_extra.at[0].set(False)
            dontcare = (
                free & ~forced & (spos + sneg == 0) & full_b[:, None]
                & ~in_extra[None, :]
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
            # --- device-side watchdog: did ANY lane advance?
            progress = jnp.any(
                do_force | do_bt | do_dec | unsat_now | done_sat | bail
            )
            stall1 = jnp.where(progress, 0, stall[0] + 1)
            return (A3, lvl3, reason3, tpos3, dvar2, dphase2, dflip2,
                    depth2, status1, nstamp, recent4, spos_eff,
                    sneg_eff, fullsw1, fsteps1, nlearn1, learned1,
                    extra1, nextra1, jnp.reshape(stall1, (1,)), it + 1)

        def cond(carry):
            status, stall, it = carry[8], carry[-2], carry[-1]
            return (
                jnp.any(status == 0) & (it < budget)
                & (stall[0] < watchdog)
            )

        init = (assign0, lvl0, reason0, tpos0, dvar0, dphase0, dflip0,
                depth0, status0, stamp0, recent0, cspos0, csneg0,
                fullsw0, fsteps0, nlearn0, learned0, extra0, nextra0,
                stall0, jnp.int32(itc0[0]))
        out = lax.while_loop(cond, body, init)
        return out[:17] + (pref0,) + out[17:20] + (
            jnp.reshape(out[20], (1,)),
        )

    return rounds


def make_resident_step(num_vars: int, max_decisions: int):
    """Jitted resident solve (cache-keyed by the caller together with
    every knob baked into the trace): ``fn(lits[C,K], adj[V1,deg],
    *state) -> state'`` over RESIDENT_STATE_FIELDS."""
    from mythril_tpu.ops.batched_sat import _require_jax

    jax, _ = _require_jax()
    return jax.jit(build_resident_rounds(
        num_vars, resident_budget(), max_decisions,
        fan=frontier_fan(), period=frontier_period(),
        watchdog=resident_watchdog_limit(),
        extra_cap=resident_extra_cap(),
    ))
