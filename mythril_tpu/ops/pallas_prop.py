"""Fused Pallas TPU kernels for batched SAT: cone-restricted DPLL.

The gather-style step in :mod:`ops.batched_sat` reads ``assign[|lit|]``
per clause literal — irregular access the VPU handles but the MXU
cannot.  This module reformulates clause evaluation as dense
*clause-incidence matmuls* so every sweep runs as systolic-array work:

- ``P[c, v] = 1`` iff variable ``v`` occurs positively in clause ``c``
  (``N`` likewise for negative occurrences), stored bf16.
- With the assignment ``A[b, v] ∈ {-1, 0, +1}`` (f32):
    ``true_cnt  = relu(A)·Pᵀ + relu(-A)·Nᵀ``   (satisfied literals)
    ``false_cnt = relu(-A)·Pᵀ + relu(A)·Nᵀ``   (falsified literals)
  A clause is a conflict when ``false_cnt == width``, a *unit* when
  unsatisfied with exactly one unknown literal, and *open* when
  unsatisfied with several unknowns; forced variables and decision
  scores come back through the transposed products — the scatter step
  is also a matmul.  Counts are exact: 0/1 bf16 products accumulate in
  f32 (``preferred_element_type``) without rounding below 2^24.

Around one such sweep per step, the jitted control loop runs a full
**batched DPLL search** — the round-3 upgrade over the earlier
BCP+WalkSAT kernel whose telemetry showed it deciding nothing on real
EVM workloads:

- per-lane trail levels ``lvl[b, v]`` and an explicit decision stack
  (``dvar/dphase/dflip [b, d]``) live in device memory;
- when a sweep reports no conflict and no forced literal, the lane
  *decides*: the free variable appearing in the most open clauses,
  with the majority polarity over those clauses (dynamic DLIS);
- a conflict backtracks chronologically: pop to the deepest unflipped
  decision, unassign every variable at or above that level, re-assert
  the flipped phase — classic DPLL, which terminates and is *complete*
  over the dispatched clause set;
- a conflict with no unflipped decision left is a sound UNSAT verdict
  even under decisions (the cone clauses are a subset of the pool, and
  a subset being unsatisfiable under the lane's assumptions makes the
  full pool unsatisfiable under them);
- a lane with no conflict, no forcing and no free variable holds a
  complete satisfying assignment for the cone — a SAT *candidate* the
  host verifies against the original terms before trusting.

Everything is mask-vectorized over lanes (one lane backtracks while a
sibling decides, in the same fused step), so the whole search runs as
one ``lax.while_loop`` of MXU sweeps — no host round-trips between
decisions.

Two lessons from earlier rounds are baked into the shape of this file:

1. **Sweep the cone, not the pool.**  One feasibility query constrains
   only its defining cone — usually a few hundred clauses of a pool of
   tens of thousands.  ``BlastContext.cone()`` extracts the per-batch
   cone on the host and the dense matrices are built over remapped cone
   variables, shrinking sweeps by orders of magnitude.

2. **Decisions, not probes.**  Measured in round 2: EVM-derived cones
   are WalkSAT-resistant (model guessing decides ~0 lanes) and BCP
   alone conflicts only on trivially dead paths.  Real verdicts need
   the search tree.

Soundness contract: UNSAT only from (a) a BCP conflict with zero
decisions or (b) an exhausted DPLL search — both sound under clause
subsets; SAT only after host-side verification of the concrete model.
Undecided lanes (step or decision budget exhausted) fall back to the
native CDCL.

Reference counterpart: this whole file replaces serial
``z3.Solver.check`` dispatch (mythril/laser/smt/solver/solver.py:47-57)
— there is nothing to port; the design follows the north star in
BASELINE.json.
"""

import functools
import logging
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger(__name__)

# Per-call dense cone caps: V and C are bucketed powers of two; the
# two bf16 incidence planes cost 4*C*V bytes of HBM.  Two tiers:
# the small tier is what CPU interpret mode (tests) can chew
# through; a real TPU gets matrices sized for its HBM/MXU —
# wide frontiers over medium cones (the lockstep north star) only fit
# the large tier.
MAX_VARS_DENSE = 4096
MAX_CLAUSES_DENSE = 1 << 15
MAX_CELLS_DENSE = 1 << 22    # 4M cells = 16 MB for the two planes
MAX_VARS_DENSE_TPU = 1 << 14
MAX_CLAUSES_DENSE_TPU = 1 << 17
MAX_CELLS_DENSE_TPU = 1 << 26  # 64M cells = 256 MB of incidence data
MAX_LANES = 64               # per-chunk cap, further shrunk for wide V
# the [B,V] assignment/level planes stay VMEM-resident across all grid
# steps; cap their footprint
MAX_LANE_CELLS = 1 << 18
# DPLL budgets.  Each step costs one incidence sweep (8 matmuls), so
# the step budget bounds dispatch latency; the decision budget bounds
# the [B, D] stack planes.  Calibrated on the captured scale-scenario
# dispatch (10.5k cone clauses / 3.2k vars, 8 lanes): completion takes
# ~1.7-2k sweeps and ~700 decisions with the don't-care cascade — the
# TPU budget doubles that for headroom; the while_loop exits early on
# decided batches, so the budget is a cap, not a cost.  Past
# DPLL_MAX_VARS the stack would be too shallow to finish realistic
# searches — those cones run BCP-only (decisions disabled, sound-UNSAT
# detection still on).
DPLL_STEPS = 4096
DPLL_STEPS_INTERPRET = 192
MAX_DECISIONS = 1024
DPLL_MAX_VARS = 8192
DPLL_MAX_VARS_INTERPRET = 2048
# chunked decisions: after DPLL_SINGLE_WINDOW single-var levels, each
# level assigns the top-K scoring free vars at once.  A conflict that
# backtracks into a bulk level taints the lane — its exhaustion is no
# longer a refutation (the discarded companions' phases were never
# explored), so tainted lanes can claim SAT (host-verified) but report
# undecided instead of UNSAT.  Completion sweeps drop ~K-fold.
DPLL_SINGLE_WINDOW = 8
DPLL_BULK_K = 16
# Round-ladder budgets: the monolithic while_loop ran every lane for as
# long as the SLOWEST lane in the batch needed (BENCH_r05: 9,698 sweeps
# for 158 lanes — one hard lane drags a full-width batch).  Budgeted
# rounds let the host retire decided lanes between rounds and re-pack
# the survivors into the smallest lane bucket that fits, so late sweeps
# run at straggler width, not batch width.  Budgets come from a FIXED
# geometric set (the last entry repeats until the tier's step budget is
# covered), so per-round shapes reuse the existing bucket grid and no
# new kernels compile after warmup.
ROUND_BUDGETS = (64, 256, 1024)
ROUND_BUDGETS_INTERPRET = (48, 144)
# Tiered cone sweeping: the hot tier (narrow clauses + rows touched by
# the assignment frontier / the last round's trail) is swept every
# step; the cold remainder joins every TIER_PERIOD-th sweep as the
# conflict/completeness check.  Soundness is preserved by gating the
# verdict-bearing transitions on full sweeps (see _dpll_round_loop):
# SAT completion, bulk decisions and the don't-care cascade only happen
# on a full-cone view, while hot-subset conflicts/forcings are sound
# unconditionally (every hot clause is a real cone clause).
TIER_PERIOD = 8
HOT_WIDTH = 3  # clauses at most this wide are always hot (unit fuel)


def pallas_enabled() -> Optional[bool]:
    """Tri-state gate: True (forced on; interpret mode on the CPU
    backend), False (forced off), None (auto: on iff the process runs
    on a TPU it was not demoted from)."""
    flag = os.environ.get("MYTHRIL_TPU_PALLAS", "").lower()
    if flag in ("1", "true", "force"):
        return True
    if flag in ("0", "false", "off"):
        return False
    return None


def _use_pallas() -> bool:
    forced = pallas_enabled()
    if forced is False:
        return False
    from mythril_tpu.ops.device_health import backend_name, device_ok

    if not device_ok():
        return False
    if backend_name() == "tpu":
        return True
    # interpret mode only when a test forces it on the CPU backend
    return bool(forced) and backend_name() == "cpu"


def _bucket(n: int, floor: int = 128) -> int:
    size = floor
    while size < n:
        size *= 2
    return size


def _tier_period() -> int:
    """Cold-sweep period (env-tunable; <= 1 disables the tier split).
    Without an operator pin the autopilot tuner may publish a bounded
    override (autopilot/tuner.py)."""
    if not os.environ.get("MYTHRIL_TPU_TIER_PERIOD", "").strip():
        from mythril_tpu.autopilot import knob_override

        tuned = knob_override("tier_period")
        if tuned is not None:
            return max(1, tuned)
    from mythril_tpu.support.env import env_int

    return env_int("MYTHRIL_TPU_TIER_PERIOD", TIER_PERIOD, floor=1)


def _ladder_budgets(total_steps: int, interpret: bool) -> list:
    """Per-round step budgets covering ``total_steps`` from the fixed
    geometric set (last entry repeats; slight overshoot is fine — the
    loop exits early on decided batches).  `MYTHRIL_TPU_ROUND_LADDER=0`
    collapses the ladder back to one monolithic round."""
    if os.environ.get("MYTHRIL_TPU_ROUND_LADDER", "1").lower() in (
        "0", "off", "false",
    ):
        return [total_steps]
    seq = ROUND_BUDGETS_INTERPRET if interpret else ROUND_BUDGETS
    budgets, spent, i = [], 0, 0
    while spent < total_steps:
        budgets.append(seq[min(i, len(seq) - 1)])
        spent += budgets[-1]
        i += 1
    return budgets


def _hot_row_mask(urow, ulit, width_arr, seed_cols) -> np.ndarray:
    """Hot-tier membership over clause rows: narrow clauses (unit fuel
    for BCP) plus every row touching a seed column (the assignment
    frontier at dispatch time; the round trail later)."""
    n_rows = len(width_arr)
    mask = (width_arr > 0) & (width_arr <= HOT_WIDTH)
    if len(urow) and len(seed_cols):
        hit = np.isin(np.abs(ulit.astype(np.int64)), seed_cols)
        touched = np.zeros(n_rows, dtype=bool)
        touched[np.unique(urow[hit])] = True
        mask = mask | touched
    return mask


def _hot_first_perm(hot_mask: np.ndarray):
    """Stable permutation packing hot rows to the row-axis prefix.
    Returns (order, new_pos): ``order[new] = old`` for width vectors,
    ``new_pos[old] = new`` for remapping ``urow`` coordinates."""
    order = np.argsort(~hot_mask, kind="stable")
    new_pos = np.empty(len(hot_mask), np.int64)
    new_pos[order] = np.arange(len(hot_mask))
    return order, new_pos


class DenseClausePool:
    """Dense incidence matrices over an explicit clause list.

    Used per-call over remapped cone clauses (the primary path) and
    directly over small whole pools in tests.
    """

    def __init__(self):
        self.P = None       # [C, V] bf16 on device
        self.N = None
        self.width = None   # [1, C] f32
        self.num_vars = 0   # V - 1 usable ids (column == var id)
        self.C = 0
        self.V = 0

    @staticmethod
    def fits_lane(C: int, V: int, tpu: bool = False) -> bool:
        """Caps for ONE lane of the per-lane batched layout (already
        bucketed shapes); the chunker bounds total [B, C, V] cells."""
        if tpu:
            return (
                C <= MAX_CLAUSES_DENSE_TPU
                and V <= MAX_VARS_DENSE_TPU
                and C * V * 8 <= MAX_CELLS_DENSE_TPU * 4
            )
        return (
            C <= MAX_CLAUSES_DENSE
            and V <= MAX_VARS_DENSE
            and C * V * 8 <= MAX_CELLS_DENSE * 4
        )

    @staticmethod
    def fits(num_clauses: int, num_vars: int, tpu: bool = False) -> bool:
        C = _bucket(max(1, num_clauses))
        V = _bucket(num_vars + 1)
        if tpu:
            return (
                C <= MAX_CLAUSES_DENSE_TPU
                and V <= MAX_VARS_DENSE_TPU
                and C * V <= MAX_CELLS_DENSE_TPU
            )
        return (
            C <= MAX_CLAUSES_DENSE
            and V <= MAX_VARS_DENSE
            and C * V <= MAX_CELLS_DENSE
        )

    def refresh(self, clauses_py: Sequence[Tuple[int, ...]], num_vars: int):
        """Tuple-list entry point (tests, mesh shards over small pools);
        the hot dispatch path uses :meth:`refresh_coords` with arrays
        straight from the native pool's CSR."""
        flat = [lit for clause in clauses_py for lit in clause]
        lits = np.fromiter(flat, dtype=np.int32, count=len(flat))
        lens = np.fromiter(
            (len(clause) for clause in clauses_py), dtype=np.int64,
            count=len(clauses_py),
        )
        indptr = np.concatenate([[0], np.cumsum(lens)])
        urow, ulit, width_arr = dedupe_clause_rows(lits, indptr)
        self.refresh_coords(
            urow, ulit, width_arr, len(clauses_py), num_vars
        )

    def refresh_coords(
        self, urow, ulit, width_arr, n_rows: int, num_vars: int
    ):
        """Build the device incidence planes from deduped (row, literal)
        coordinate arrays (see :func:`dedupe_clause_rows`)."""
        C = _bucket(max(1, n_rows))
        V = _bucket(num_vars + 1)
        # host ships only literal coordinates (a few hundred KB); the
        # [C, V] incidence planes (hundreds of MB at the TPU tier) are
        # scatter-built on device — building them as host numpy and
        # uploading four dense copies dominated dispatch latency
        width = np.zeros((1, C), dtype=np.float32)
        width[0, :n_rows] = width_arr
        pos = ulit > 0
        pos_r, pos_c = urow[pos], ulit[pos]
        neg_r, neg_c = urow[~pos], -ulit[~pos]
        from mythril_tpu.ops.device_placement import place

        build = _make_incidence_builder(
            C, V,
            _bucket(max(1, len(pos_r)), floor=256),
            _bucket(max(1, len(neg_r)), floor=256),
        )
        # the dispatch ships only literal coordinates; the [C, V]
        # planes are scatter-built on device (counted h2d = coords)
        from mythril_tpu.ops.batched_sat import dispatch_stats

        dispatch_stats.h2d_bytes += (
            4 * 2 * (build.n_pos + build.n_neg) + int(width.nbytes)
        )
        # committed inputs pin the jitted build (and everything
        # downstream that consumes its outputs) to the corpus shard's
        # device — contract-level data parallelism over chips
        self.P, self.N, self.width = build(
            place(_pad_coords(pos_r, build.n_pos)),
            place(_pad_coords(pos_c, build.n_pos)),
            place(_pad_coords(neg_r, build.n_neg)),
            place(_pad_coords(neg_c, build.n_neg)),
            place(width),
        )
        self.num_vars = V - 1
        self.C, self.V = C, V


def dedupe_clause_rows(lits: np.ndarray, indptr: np.ndarray):
    """Vectorized clause-row normalization for the incidence builds.

    Input is a CSR literal layout (row i = clause i).  Returns
    ``(urow, ulit, width)`` where (urow, ulit) are the unique
    (row, literal) coordinate pairs with tautologous rows removed
    entirely, and ``width[i]`` is the count of UNIQUE literals of row i
    (0 for tautologies — an all-zero incidence row is inert).  The
    incidence cell collapses duplicate literals, so width must count
    unique ones or conflicts/units are missed."""
    n_rows = len(indptr) - 1
    if n_rows == 0 or len(lits) == 0:
        empty = np.empty(0, dtype=np.int64)
        return empty, empty.astype(np.int32), np.zeros(n_rows, np.float32)
    row = np.repeat(
        np.arange(n_rows, dtype=np.int64), np.diff(indptr)
    )
    # unique (row, literal) pairs via a packed key (|lit| < 2**32)
    key = row << np.int64(34)
    key += lits.astype(np.int64) + (np.int64(1) << np.int64(33))
    _, first = np.unique(key, return_index=True)
    urow = row[first]
    ulit = lits[first]
    # tautology = some (row, var) present with both polarities; pairs
    # are unique now, so a (row, |lit|) count of 2 means both signs
    vkey = (urow << np.int64(34)) + np.abs(ulit.astype(np.int64))
    vals, counts = np.unique(vkey, return_counts=True)
    width = np.zeros(n_rows, dtype=np.float32)
    if np.any(counts > 1):
        taut_rows = np.unique(vals[counts > 1] >> np.int64(34))
        keep = ~np.isin(urow, taut_rows)
        urow, ulit = urow[keep], ulit[keep]
    np.add.at(width, urow, 1.0)
    return urow, ulit.astype(np.int32), width


def remap_cone_csr(ctx, clause_ids, cone_vars):
    """Fetch the given pool clauses from the native CSR store and remap
    variable ids onto dense columns: anchor var 1 -> column 1,
    ``cone_vars[i]`` (sorted) -> column ``i + 2``.  Every variable in a
    cone clause is in the cone by construction of the BFS.  Returns the
    deduped coordinates of :func:`dedupe_clause_rows`."""
    lits, indptr = ctx.pool.subset_csr(clause_ids)
    av = np.abs(lits).astype(np.int64)
    col = np.where(av == 1, 1, np.searchsorted(cone_vars, av) + 2)
    remapped = np.where(lits < 0, -col, col).astype(np.int32)
    return dedupe_clause_rows(remapped, indptr)


def assumption_columns(cone_vars: np.ndarray, lits) -> np.ndarray:
    """Dense columns of assumption literals under the same remap;
    returns signed column ids (sign = literal polarity)."""
    arr = np.fromiter(lits, dtype=np.int64, count=len(lits))
    av = np.abs(arr)
    col = np.where(av == 1, 1, np.searchsorted(cone_vars, av) + 2)
    return np.where(arr < 0, -col, col)


def _pad_coords(values, size: int) -> np.ndarray:
    """Pad a coordinate list to its bucket with (0, 0) writes — cell
    (0, 0) is row 0 x column 0, and column 0 is never a variable, so a
    spurious 1 there never changes counts (A[:, 0] stays 0) and forced
    votes/scores for column 0 are masked off by ``col > 1``."""
    arr = np.zeros(size, dtype=np.int32)
    arr[: len(values)] = values
    return arr


@functools.lru_cache(maxsize=32)
def _make_incidence_builder(C: int, V: int, n_pos: int, n_neg: int):
    """Jitted device-side incidence build for fixed shapes: scatter the
    literal coordinates into bf16 [C, V] planes."""
    import jax
    import jax.numpy as jnp

    def build(pos_r, pos_c, neg_r, neg_c, width):
        P = jnp.zeros((C, V), dtype=jnp.bfloat16).at[pos_r, pos_c].set(1)
        N = jnp.zeros((C, V), dtype=jnp.bfloat16).at[neg_r, neg_c].set(1)
        return P, N, jnp.asarray(width)

    fn = jax.jit(build)
    fn.n_pos = n_pos
    fn.n_neg = n_neg
    return fn


def _tile_c(C: int, V: int) -> int:
    """Clause-tile height: keep the two bf16 tiles of [TC, V] under a
    few MB of VMEM.  Floor 128: the width row's block is [1, TC] and
    Mosaic requires the last block dim be a multiple of 128.  Never
    exceeds C (both are powers of two, so TC always divides C)."""
    return min(C, max(128, min(256, (1 << 19) // V)))


def _sweep_vmem_limit(V: int, B: int, TC: int, scores: bool) -> int:
    """Scoped-VMEM budget of one sweep.  Every block is double-buffered:
    the [B, V] f32 assignment in, three (five with scores) [B, V] f32
    accumulators out, and the two [TC, V] bf16 incidence tiles; 16 MiB
    more covers the kernel's [B, V] temporaries.  The compiler's default
    scope (16 MiB) refuses V >= 8192 at the TPU caps; the widest shape
    the caps admit (V=16384, B=16) asks for 44 MiB of v5e's 128."""
    planes = 1 + (5 if scores else 3)
    blocks = 2 * (planes * B * V * 4 + 2 * TC * V * 2)
    return blocks + (16 << 20)


def _make_dpll_sweep(
    C: int, V: int, B: int, TC: int, interpret: bool, scores: bool
):
    """One full clause scan over a partial assignment, tiled over the
    clause axis: returns forced-literal votes, conflict flags, and —
    when ``scores`` — open-clause participation scores (the dynamic
    decision heuristic).  BCP-only callers skip the two score matmuls
    and their [B, V] accumulators entirely (they run on the largest
    cone tier, which can least afford waste).

    Grid step i streams tile i of P/N (and their transposes) HBM→VMEM,
    runs the incidence matmuls on the MXU, and accumulates into
    revisited output blocks (TPU grids run sequentially, so
    read-modify-write across grid steps is well-defined).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    natural = (((1,), (0,)), ((), ()))  # [M,K] x [K,N] -> [M,N]
    # contract the V axes of [B,V] x [TC,V] -> [B,TC]: the same P/N
    # tiles serve both matmul directions, so the kernel streams two
    # incidence planes instead of four (the sweep is HBM-bound)
    by_v = (((1,), (1,)), ((), ()))

    def kernel(p_ref, n_ref, w_ref, a_ref, *out_refs):
        if scores:
            fpos_ref, fneg_ref, conf_ref, spos_ref, sneg_ref = out_refs
        else:
            fpos_ref, fneg_ref, conf_ref = out_refs
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            fpos_ref[:] = jnp.zeros((B, V), dtype=jnp.float32)
            fneg_ref[:] = jnp.zeros((B, V), dtype=jnp.float32)
            conf_ref[:] = jnp.zeros((B, 1), dtype=jnp.float32)
            if scores:
                spos_ref[:] = jnp.zeros((B, V), dtype=jnp.float32)
                sneg_ref[:] = jnp.zeros((B, V), dtype=jnp.float32)

        P = p_ref[:]    # [TC, V]
        N = n_ref[:]
        width = w_ref[:]  # [1, TC]
        A = a_ref[:]      # [B, V]

        pos = jnp.maximum(A, 0.0).astype(jnp.bfloat16)
        neg = jnp.maximum(-A, 0.0).astype(jnp.bfloat16)
        true_cnt = lax.dot_general(
            pos, P, by_v, preferred_element_type=jnp.float32
        ) + lax.dot_general(
            neg, N, by_v, preferred_element_type=jnp.float32
        )  # [B, TC]
        false_cnt = lax.dot_general(
            neg, P, by_v, preferred_element_type=jnp.float32
        ) + lax.dot_general(
            pos, N, by_v, preferred_element_type=jnp.float32
        )
        real = width > 0.5
        all_false = real & (false_cnt > width - 0.5)
        unk_cnt = width - true_cnt - false_cnt
        unsat_yet = (true_cnt < 0.5) & real
        unit = unsat_yet & (unk_cnt > 0.5) & (unk_cnt < 1.5)
        u = unit.astype(jnp.bfloat16)
        fpos_ref[:] += lax.dot_general(
            u, P, natural, preferred_element_type=jnp.float32
        )
        fneg_ref[:] += lax.dot_general(
            u, N, natural, preferred_element_type=jnp.float32
        )
        if scores:
            # decision scores: membership of each variable in open
            # clauses, split by polarity (argmax picks the var, the
            # majority polarity picks the phase)
            open_c = unsat_yet & (unk_cnt > 1.5)
            o = open_c.astype(jnp.bfloat16)
            spos_ref[:] += lax.dot_general(
                o, P, natural, preferred_element_type=jnp.float32
            )
            sneg_ref[:] += lax.dot_general(
                o, N, natural, preferred_element_type=jnp.float32
            )
        conf_ref[:] = jnp.maximum(
            conf_ref[:],
            jnp.any(all_false, axis=1, keepdims=True).astype(jnp.float32),
        )

    grid = (C // TC,)
    vm = pltpu.VMEM
    full = lambda i: (0, 0)  # noqa: E731 — revisit the same block
    plane = pl.BlockSpec((B, V), full, memory_space=vm)
    flag = pl.BlockSpec((B, 1), full, memory_space=vm)
    plane_shape = jax.ShapeDtypeStruct((B, V), jnp.float32)
    flag_shape = jax.ShapeDtypeStruct((B, 1), jnp.float32)
    out_specs = (plane, plane, flag) + ((plane, plane) if scores else ())
    out_shape = (plane_shape, plane_shape, flag_shape) + (
        (plane_shape, plane_shape) if scores else ()
    )
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TC, V), lambda i: (i, 0), memory_space=vm),
            pl.BlockSpec((TC, V), lambda i: (i, 0), memory_space=vm),
            pl.BlockSpec((1, TC), lambda i: (0, i), memory_space=vm),
            pl.BlockSpec((B, V), full, memory_space=vm),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_sweep_vmem_limit(V, B, TC, scores)
        ),
        interpret=interpret,
    )
    return call


#: field order of the resumable solver state (see _dpll_round_loop);
#: drivers index status/active out of round outputs by these positions.
#: ``pref`` is the warm-start decision-phase plane ([B, V] f32, 0 = no
#: preference): it rides the state so lane compaction carries it, is
#: never written by the kernel, and only biases which polarity a
#: decision tries first (ops/incremental.py — verdicts untouched).
DPLL_STATE_FIELDS = (
    "A", "lvl", "dvar", "dphase", "dflip", "dbulk", "depth", "status",
    "taint", "active", "pref",
)
_STATUS_IDX = DPLL_STATE_FIELDS.index("status")
_ACTIVE_IDX = DPLL_STATE_FIELDS.index("active")


def _dpll_state0(A0: np.ndarray, D: int, n_real: int,
                 pref_row=None) -> list:
    """Host-side zero state for a round ladder over ``A0 [B, V]``;
    rows past ``n_real`` are bucket padding, retired from step 0.
    ``pref_row`` ([V] or broadcastable) seeds the warm-start phase
    plane for every lane."""
    B, V = A0.shape
    pref = np.zeros((B, V), np.float32)
    if pref_row is not None:
        pref[:] = np.asarray(pref_row, np.float32)
    state = [
        A0.astype(np.float32, copy=True),
        np.zeros((B, V), np.int32),
        np.zeros((B, D), np.int32),
        np.zeros((B, D), np.float32),
        np.zeros((B, D), np.float32),
        np.zeros((B, D), np.float32),
        np.zeros((B, 1), np.int32),
        np.zeros((B, 1), np.int32),
        np.zeros((B, 1), np.float32),
        np.zeros((B, 1), np.int32),
        pref,
    ]
    state[_STATUS_IDX][n_real:] = 3
    return state


def _dpll_round_loop(sweep, B, V, budget, max_decisions, sweep_hot=None,
                     tier_period=1):
    """Resumable DPLL control loop around a sweep callable.

    ``sweep(P, N, width, A)`` returns (fpos, fneg, conf[, spos, sneg])
    as [B, V] / [B, 1] planes; the loop is agnostic to how the clause
    scan is realized (tiled Pallas kernel over a shared [C, V] pool, or
    batched XLA dots over per-lane [B, C, V] planes).

    Returns the raw (unjitted) round function
    ``rounds(P, N, width, *state) -> (*state', steps_used)`` over the
    DPLL_STATE_FIELDS tuple, so the host can run budgeted rounds,
    retire decided lanes between them and re-pack survivors into a
    smaller lane bucket (the round ladder).  Status is RAW here:
    0 live, 1 SAT candidate, 2 sound UNSAT, 3 retired-undecided
    (budget/taint bail — the ladder must not re-enter such lanes);
    ``active`` counts per-lane live sweeps for the utilization split.

    ``sweep_hot`` (with ``tier_period > 1``) enables tiered sweeping:
    steps where ``step % tier_period != 0`` scan only the hot clause
    prefix.  Hot-subset conflicts, forcings and exhaustion verdicts
    are sound unconditionally (hot clauses are real cone clauses, and a
    subset conflict refutes the superset), but SAT completion, bulk
    decisions and the don't-care cascade need the full-cone view, so
    those transitions are gated on full sweeps.
    """
    import jax.numpy as jnp
    from jax import lax

    decisions_on = max_decisions > 0
    D = max(1, min(max_decisions, V))  # stack planes ([B, D])
    tiered = sweep_hot is not None and tier_period > 1

    def dense_rounds(P, N, width, A0, lvl0, dvar0, dphase0, dflip0,
                     dbulk0, depth0, status0, taint0, active0, pref0):
        col = lax.broadcasted_iota(jnp.int32, (B, V), 1)
        dcol = lax.broadcasted_iota(jnp.int32, (B, D), 1)  # slot l ↔ level l+1
        krow = jnp.arange(DPLL_BULK_K)[None, :]            # [1, K]

        def body(carry):
            (A, lvl, dvar, dphase, dflip, dbulk, depth, status, taint,
             sweeps, pref, step) = carry
            if tiered:
                full_view = (step % tier_period) == 0
                outs = lax.cond(
                    full_view,
                    lambda a: sweep(P, N, width, a),
                    lambda a: sweep_hot(P, N, width, a),
                    A,
                )
            else:
                full_view = jnp.bool_(True)
                outs = sweep(P, N, width, A)
            if decisions_on:
                fpos, fneg, conf, spos, sneg = outs
            else:
                fpos, fneg, conf = outs
            free = (A == 0.0) & (col > 1)  # col 1 = constant-TRUE anchor
            force_pos = (fpos > 0.5) & free
            force_neg = (fneg > 0.5) & free
            contra = jnp.any(force_pos & force_neg, axis=1, keepdims=True)
            conflict = (conf > 0.5) | contra               # [B,1]
            has_force = jnp.any(
                force_pos | force_neg, axis=1, keepdims=True
            )
            open_any = jnp.any(free, axis=1, keepdims=True)
            active = status == 0                           # [B,1]

            # --- conflict: backtrack to the deepest unflipped decision
            held = dcol < depth                            # [B,D]
            unflipped = held & (dflip < 0.5)
            Lm = jnp.max(
                jnp.where(unflipped, dcol + 1, 0), axis=1, keepdims=True
            )                                              # [B,1], 0 = none
            unsat_now = active & conflict & (Lm == 0)
            do_bt = active & conflict & (Lm > 0)
            bslot = jnp.maximum(Lm - 1, 0)
            bvar = jnp.take_along_axis(dvar, bslot, axis=1)      # [B,1]
            bphase = -jnp.take_along_axis(dphase, bslot, axis=1)
            A1 = jnp.where(do_bt & (A != 0.0) & (lvl >= Lm), 0.0, A)
            A1 = jnp.where(do_bt & (col == bvar), bphase, A1)
            lvl1 = jnp.where(do_bt & (col == bvar), Lm, lvl)
            popped = do_bt & (dcol >= Lm)                  # slots above Lm
            at_b = do_bt & (dcol == bslot)
            # flipping (or popping) a bulk level discards its companion
            # branches unexplored: the lane's exhaustion is no longer a
            # refutation
            bulk_popped = jnp.any(
                popped & (dbulk > 0.5), axis=1, keepdims=True
            ) | (jnp.take_along_axis(dbulk, bslot, axis=1) > 0.5)
            taint1 = jnp.where(do_bt & bulk_popped, 1.0, taint)
            dvar1 = jnp.where(popped, 0, dvar)
            dphase1 = jnp.where(popped, 0.0, jnp.where(at_b, bphase, dphase))
            dflip1 = jnp.where(popped, 0.0, jnp.where(at_b, 1.0, dflip))
            dbulk1 = jnp.where(popped | at_b, 0.0, dbulk)
            depth1 = jnp.where(do_bt, Lm, depth)

            # --- no conflict, forced literals: assign them at this level
            # (they are implied by pre-sweep assignments, so they belong
            # to the pre-decision level even when a decision is fused
            # into the same sweep below)
            do_force = active & ~conflict & has_force
            forced = force_pos | force_neg
            assigned_now = do_force & forced & ~(force_pos & force_neg)
            delta = jnp.where(force_pos, 1.0, -1.0)
            A2 = jnp.where(assigned_now, delta, A1)
            lvl2 = jnp.where(assigned_now, depth, lvl1)

            # --- decide at BCP quiescence (dynamic DLIS vars +
            # polarity).  Measured on the captured scale dispatch:
            # fusing decisions into forcing sweeps (speculating on
            # stale scores mid-propagation) *increased* total sweeps
            # ~2.5x through conflict/redo churn — classic alternation
            # wins even though carry chains ripple one level per sweep.
            want = active & ~conflict & open_any & ~has_force
            if decisions_on and tiered:
                # hot-quiescence gate: when the HOT view offers no open
                # clause to score, deciding would burn blind levels on
                # cold-only vars (measured: conflict/redo churn that
                # starves completion) — wait for the full-cone sweep
                hot_open = jnp.any(
                    spos + sneg > 0.5, axis=1, keepdims=True
                )
                want = want & (full_view | hot_open)
            if decisions_on:
                can = depth < D
                # bulk levels speculate on the FULL score view; a hot
                # sweep's partial scores keep levels single-var so
                # exhaustion stays a refutation without taint
                in_bulk = (depth >= DPLL_SINGLE_WINDOW) & full_view
                do_dec = want & can
                bail = want & ~can
                score = jnp.where(
                    free & ~forced, spos + sneg + 1.0, -1.0
                )
                vals, idxs = lax.top_k(score, DPLL_BULK_K)  # [B,K]
                # single-var levels inside the refutation window keep
                # exhaustion sound; past it, levels take the top-K vars
                # at once (taint handles the lost refutation power)
                keep = (vals > 0.0) & ((krow == 0) | in_bulk)
                any_kept = jnp.any(keep, axis=1, keepdims=True)
                do_dec = do_dec & any_kept
                chosen = jnp.any(
                    (col[:, :, None] == idxs[:, None, :])
                    & keep[:, None, :],
                    axis=2,
                )                                           # [B,V]
                # warm start: a parent model's phase wins over the DLIS
                # majority where one exists (search-order bias only —
                # the flip is still explored on backtrack)
                ph_full = jnp.where(
                    pref != 0.0, pref,
                    jnp.where(spos >= sneg, 1.0, -1.0),
                )
                primary = idxs[:, :1]
                phase = jnp.take_along_axis(ph_full, primary, axis=1)
                # a level is "bulk" (taints on backtrack) only when it
                # takes >= 2 genuinely-constrained vars (score >= 2);
                # don't-care companions (score == 1) provably cannot
                # affect any open clause, so flipping just the primary
                # remains a valid refutation of the level
                real_keep = keep & (vals > 1.5)
                is_bulk = (
                    jnp.sum(real_keep.astype(jnp.int32), axis=1,
                            keepdims=True) > 1
                ).astype(jnp.float32)
                ndepth = depth + 1
                # don't-care cascade: a free var in NO open clause has
                # every containing clause already satisfied, so any
                # phase is safe — assign them all at the new level (they
                # pop with it on backtrack).  EVM cones are mostly
                # don't-cares once the constrained core is satisfied;
                # without this, completion costs one decision per var.
                # Full-view sweeps only: a var with zero HOT-view score
                # may still sit in an open cold clause, so the "provably
                # safe" argument needs the whole cone.
                dontcare = free & ~forced & (spos + sneg < 0.5) & full_view
                newly = do_dec & (dontcare | chosen)
                A3 = jnp.where(
                    newly, jnp.where(chosen, ph_full, 1.0), A2
                )
                lvl3 = jnp.where(newly, ndepth, lvl2)
                at_new = do_dec & (dcol == depth)
                dvar2 = jnp.where(at_new, primary, dvar1)
                dphase2 = jnp.where(at_new, phase, dphase1)
                dflip2 = jnp.where(at_new, 0.0, dflip1)
                dbulk2 = jnp.where(at_new, is_bulk, dbulk1)
                depth2 = jnp.where(do_dec, ndepth, depth1)
            else:
                bail = want
                A3, lvl3 = A2, lvl2
                dvar2, dphase2, dflip2, depth2 = dvar1, dphase1, dflip1, depth1
                dbulk2 = dbulk1

            # --- quiet and complete: SAT candidate.  A hot sweep's
            # conflict flag covers only the hot subset, so completion
            # is only claimed on a full-cone view.
            done_sat = active & ~conflict & ~has_force & ~open_any \
                & full_view

            # tainted exhaustion is NOT a refutation — report undecided
            status1 = jnp.where(
                unsat_now, jnp.where(taint1 > 0.5, 3, 2), status
            )
            status1 = jnp.where(done_sat, 1, status1)
            status1 = jnp.where(bail, 3, status1)  # 3 = budget-bailed
            sweeps1 = sweeps + active.astype(jnp.int32)
            return (A3, lvl3, dvar2, dphase2, dflip2, dbulk2, depth2,
                    status1, taint1, sweeps1, pref, step + 1)

        def cond(carry):
            status, step = carry[_STATUS_IDX], carry[-1]
            return jnp.any(status == 0) & (step < budget)

        init = (
            A0, lvl0, dvar0, dphase0, dflip0, dbulk0, depth0, status0,
            taint0, active0, pref0, jnp.int32(0),
        )
        out = lax.while_loop(cond, body, init)
        return out[:-1] + (out[-1],)

    return dense_rounds


def _dpll_solve_loop(sweep, B, V, steps, max_decisions):
    """Legacy one-shot wrapper over :func:`_dpll_round_loop`: zero
    state in, mapped status out (3 = bailed becomes 0 = undecided)."""
    import jax
    import jax.numpy as jnp

    rounds = _dpll_round_loop(sweep, B, V, steps, max_decisions)
    D = max(1, min(max_decisions, V))

    def solve(P, N, width, A0):
        z = jnp.zeros
        out = rounds(
            P, N, width, A0,
            z((B, V), dtype=jnp.int32),
            z((B, D), dtype=jnp.int32),
            z((B, D), dtype=jnp.float32),
            z((B, D), dtype=jnp.float32),
            z((B, D), dtype=jnp.float32),
            z((B, 1), dtype=jnp.int32),
            z((B, 1), dtype=jnp.int32),
            z((B, 1), dtype=jnp.float32),
            z((B, 1), dtype=jnp.int32),
            z((B, V), dtype=jnp.float32),  # no warm-start preference
        )
        A, status, steps_used = out[0], out[_STATUS_IDX], out[-1]
        status = jnp.where(status == 3, 0, status)  # bailed = undecided
        return A, status, steps_used

    return jax.jit(solve)



@functools.lru_cache(maxsize=16)
def make_dense_solve(
    C: int, V: int, B: int, steps: int, interpret: bool,
    max_decisions: int = MAX_DECISIONS,
):
    """Build the DPLL solve function for fixed (clauses, vars, lanes).

    Returns fn(P[C,V]bf16, N[C,V]bf16, width[1,C]f32, A0[B,V]f32)
    -> (A[B,V]f32, status[B,1]i32, steps_used i32) with
    status 2 = UNSAT (BCP conflict at zero decisions OR exhausted
    search — both sound under clause subsets), 1 = complete satisfying
    assignment for the device clause set (host must verify against the
    original terms), 0 = undecided (budget).  The clause scans run as
    tiled Pallas kernels; the DPLL control loop is plain lax around
    them (everything compiles to one XLA program).  The search is
    deterministic.

    ``max_decisions=0`` disables the search (BCP-only, for cones past
    the stack budget) and skips the score matmuls in the sweep.
    """
    TC = _tile_c(C, V)
    sweep = _make_dpll_sweep(C, V, B, TC, interpret, max_decisions > 0)
    return _dpll_solve_loop(sweep, B, V, steps, max_decisions)


@functools.lru_cache(maxsize=64)
def make_dense_rounds(
    C: int, V: int, B: int, budget: int, interpret: bool,
    max_decisions: int = MAX_DECISIONS, hot_c: int = 0,
    tier_period: int = 1,
):
    """Resumable round variant of :func:`make_dense_solve` for the
    round-ladder driver: fn(P, N, width, *state) -> (*state',
    steps_used) with RAW status (see _dpll_round_loop).

    ``hot_c > 0`` builds a second Pallas sweep over only the first
    ``hot_c`` clause rows (the hot tier packed to the row prefix by the
    caller; must be a multiple of the clause tile) and sweeps the full
    pool every ``tier_period``-th step only.
    """
    import jax

    TC = _tile_c(C, V)
    scores = max_decisions > 0
    sweep = _make_dpll_sweep(C, V, B, TC, interpret, scores)
    sweep_hot = None
    if hot_c and tier_period > 1 and TC <= hot_c < C:
        sweep_hot = _make_dpll_sweep(hot_c, V, B, TC, interpret, scores)
    return jax.jit(_dpll_round_loop(
        sweep, B, V, budget, max_decisions, sweep_hot, tier_period
    ))


@functools.lru_cache(maxsize=16)
def make_batched_solve(
    C: int, V: int, B: int, steps: int,
    max_decisions: int = MAX_DECISIONS,
):
    """Per-lane-cone DPLL: each lane owns its own remapped incidence
    planes ``P/N [B, C, V]`` and the sweeps are *batched* matmuls.

    Frontier batches are usually block-diagonal — sibling queries share
    a prefix, but across functions/guards the cones are disjoint — so a
    union-cone dense matrix wastes most of its cells (and the HBM
    bandwidth to stream them) on cross-lane zeros.  Remapping each lane
    into its own compact variable space makes total sweep data
    ``Σ C_l·V_l`` instead of ``(Σ C_l)·(Σ V_l)``: measured 16x less on
    a 16-lane disjoint-guard dispatch.  Plain jnp/lax (XLA lowers
    batched dots onto the MXU and handles the streaming); the DPLL
    control flow is identical to ``make_dense_solve``.

    Returns fn(P[B,C,V]bf16, N[B,C,V]bf16, width[B,C]f32, A0[B,V]f32)
    -> (A[B,V]f32, status[B,1]i32, steps_used i32).
    """
    sweep = _make_batched_sweep(max_decisions > 0)
    return _dpll_solve_loop(sweep, B, V, steps, max_decisions)


@functools.lru_cache(maxsize=32)
def make_batched_rounds(
    C: int, V: int, B: int, budget: int,
    max_decisions: int = MAX_DECISIONS, hot_c: int = 0,
    tier_period: int = 1,
):
    """Resumable round variant of :func:`make_batched_solve` (same
    state contract as make_dense_rounds).  ``hot_c`` slices the leading
    ``hot_c`` rows of each lane's plane for the hot-tier sweeps — the
    caller packs each lane's hot rows to its row prefix."""
    import jax

    sweep = _make_batched_sweep(max_decisions > 0)
    sweep_hot = None
    if hot_c and tier_period > 1 and hot_c < C:
        base = sweep

        def sweep_hot(P, N, width, A):  # noqa: F811 — tier closure
            return base(P[:, :hot_c], N[:, :hot_c], width[:, :hot_c], A)

    return jax.jit(_dpll_round_loop(
        sweep, B, V, budget, max_decisions, sweep_hot, tier_period
    ))


def _make_batched_sweep(decisions_on: bool):
    """One batched clause scan over per-lane incidence planes
    ([B, C, V] dots; XLA streams and MXU-lowers them)."""
    import jax.numpy as jnp
    from jax import lax

    # lhs [B,V] x rhs [B,C,V], contract V, batch B -> [B,C]
    by_v = (((1,), (2,)), ((0,), (0,)))
    # lhs [B,C] x rhs [B,C,V], contract C, batch B -> [B,V]
    by_c = (((1,), (1,)), ((0,), (0,)))

    def sweep(P, N, width, A):
        pos = jnp.maximum(A, 0.0).astype(jnp.bfloat16)
        neg = jnp.maximum(-A, 0.0).astype(jnp.bfloat16)
        true_cnt = lax.dot_general(
            pos, P, by_v, preferred_element_type=jnp.float32
        ) + lax.dot_general(
            neg, N, by_v, preferred_element_type=jnp.float32
        )  # [B, C]
        false_cnt = lax.dot_general(
            neg, P, by_v, preferred_element_type=jnp.float32
        ) + lax.dot_general(
            pos, N, by_v, preferred_element_type=jnp.float32
        )
        real = width > 0.5
        all_false = real & (false_cnt > width - 0.5)
        unk_cnt = width - true_cnt - false_cnt
        unsat_yet = (true_cnt < 0.5) & real
        unit = unsat_yet & (unk_cnt > 0.5) & (unk_cnt < 1.5)
        u = unit.astype(jnp.bfloat16)
        fpos = lax.dot_general(
            u, P, by_c, preferred_element_type=jnp.float32
        )
        fneg = lax.dot_general(
            u, N, by_c, preferred_element_type=jnp.float32
        )
        conf = jnp.any(all_false, axis=1, keepdims=True).astype(
            jnp.float32
        )
        if decisions_on:
            open_c = unsat_yet & (unk_cnt > 1.5)
            o = open_c.astype(jnp.bfloat16)
            spos = lax.dot_general(
                o, P, by_c, preferred_element_type=jnp.float32
            )
            sneg = lax.dot_general(
                o, N, by_c, preferred_element_type=jnp.float32
            )
            return fpos, fneg, conf, spos, sneg
        return fpos, fneg, conf

    return sweep


@functools.lru_cache(maxsize=32)
def _make_lane_incidence_builder(B: int, C: int, V: int, n_pos: int,
                                 n_neg: int):
    """Jitted device-side per-lane incidence build: scatter (lane, row,
    col) coordinates into bf16 [B, C, V] planes."""
    import jax
    import jax.numpy as jnp

    def build(pos_l, pos_r, pos_c, neg_l, neg_r, neg_c, width):
        P = jnp.zeros((B, C, V), dtype=jnp.bfloat16).at[
            pos_l, pos_r, pos_c
        ].set(1)
        N = jnp.zeros((B, C, V), dtype=jnp.bfloat16).at[
            neg_l, neg_r, neg_c
        ].set(1)
        return P, N, jnp.asarray(width)

    fn = jax.jit(build)
    fn.n_pos = n_pos
    fn.n_neg = n_neg
    return fn


def _run_dense_ladder(
    round_fn,
    planes,
    A0: np.ndarray,
    n_real: int,
    max_decisions: int,
    steps_total: int,
    interpret: bool,
    hot_c: int = 0,
    lane_floor: int = 8,
    compact_planes=None,
    grow_hot=None,
    pref_row=None,
):
    """Host driver for the round ladder over a dense solve.

    Runs ``round_fn(B, budget, hot_c)`` for the geometric budget
    sequence; between rounds decided lanes are retired (their final
    assignment captured), survivors are compacted to the bucket prefix
    and re-packed into the smallest lane bucket that fits, so one
    straggler lane stops dragging a full-width batch through the MXU.

    - ``planes`` are passed to the round function verbatim;
      ``compact_planes(planes, idx)`` re-gathers per-lane planes on
      lane compaction (None for lane-shared planes).
    - ``grow_hot(live_A, hot_c) -> (planes, hot_c) | None`` lets the
      caller fold the round's trail into the hot tier (union layout).

    Telemetry lands on DispatchStats: ``rounds``, ``repacks``,
    ``device_sweeps`` (loop iterations), ``lane_sweeps_total``
    (iterations x bucket width — the MXU work actually burned) and
    ``lane_sweeps_active`` (per-lane live sweeps — the work that could
    have decided something).

    Returns (status[n_real] int32 with bails mapped to 0, final
    A[n_real, V] float32).
    """
    from mythril_tpu.observability import spans as obs
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.resilience import faults
    from mythril_tpu.resilience.checkpoint import drain_requested
    from mythril_tpu.resilience.watchdog import raise_if_cancelled

    B, V = A0.shape
    D = max(1, min(max_decisions, V))
    state = _dpll_state0(A0, D, n_real, pref_row)
    # per-dispatch lane payload: assumption-seeded assignment plane
    # (the incidence planes are accounted at their build sites)
    dispatch_stats.h2d_bytes += int(A0.nbytes)
    statuses_out = np.zeros(n_real, np.int32)
    A_out = np.zeros((n_real, V), np.float32)
    live = np.arange(n_real)

    def commit(local_rows, st, act, A_host):
        nonlocal_sum = 0
        for local in local_rows:
            statuses_out[live[local]] = st[local]
            A_out[live[local]] = A_host[local]
            nonlocal_sum += int(act[local])
        return nonlocal_sum

    for budget in _ladder_budgets(steps_total, interpret):
        if live.size == 0:
            break
        # cooperative checkpoints: the whole ladder runs inside one
        # supervised "pallas" dispatch, so an abandoned worker bails
        # between rounds instead of racing the host on shared state —
        # and a graceful drain lands here too, retiring survivors
        # undecided so a final checkpoint can be written
        raise_if_cancelled()
        if drain_requested():
            # SIGTERM drain or an expired per-request budget (serve
            # deadline) — stamp the abandonment on the span timeline
            obs.instant("pallas.drain", cat="sweep",
                        lanes=int(live.size), bucket=B)
            break
        faults.maybe_fault_dispatch()
        # int(out[-1]) blocks until the round finished, so the span
        # brackets the real device wall for this budget rung
        with obs.span("pallas.round", cat="sweep", budget=budget,
                      bucket=B, lanes=int(live.size)):
            fn = round_fn(B, budget, hot_c)
            dispatch_stats.device_dispatch_calls += 1
            out = fn(*planes, *state)
            with obs.wait("dense.fetch"):
                state, steps_used = list(out[:-1]), int(out[-1])
        dispatch_stats.rounds += 1
        dispatch_stats.device_sweeps += steps_used
        dispatch_stats.lane_sweeps_total += steps_used * B
        st = np.asarray(state[_STATUS_IDX])[:, 0]
        done = st[: live.size] != 0
        if not done.any() and grow_hot is None:
            continue
        A_host = np.asarray(state[0])
        if done.any():
            act = np.asarray(state[_ACTIVE_IDX])[:, 0]
            dispatch_stats.lane_sweeps_active += commit(
                np.nonzero(done)[0], st, act, A_host
            )
            keep = np.nonzero(~done)[0]
            if keep.size == 0:
                live = keep
                break
            live = live[keep]
            B_new = max(
                lane_floor, _bucket(int(keep.size), floor=lane_floor)
            )
            idx = np.concatenate(
                [keep, np.repeat(keep[:1], B_new - keep.size)]
            )
            new_state = [np.ascontiguousarray(np.asarray(a)[idx])
                         for a in state]
            new_state[_STATUS_IDX][keep.size:] = 3  # pads stay inert
            if B_new < B:
                dispatch_stats.repacks += 1
            B = B_new
            state = new_state
            if compact_planes is not None:
                planes = compact_planes(planes, idx)
        else:
            keep = np.arange(live.size)
        if grow_hot is not None:
            grown = grow_hot(A_host[keep], hot_c)
            if grown is not None:
                planes, hot_c = grown
    if live.size:
        st = np.asarray(state[_STATUS_IDX])[:, 0]
        act = np.asarray(state[_ACTIVE_IDX])[:, 0]
        A_host = np.asarray(state[0])
        dispatch_stats.lane_sweeps_active += commit(
            range(live.size), st, act, A_host
        )
    return np.where(statuses_out == 3, 0, statuses_out), A_out


class PallasSatBackend:
    """Drives the fused kernels over per-call cone problems; same verdict
    contract as BatchedSatBackend (False = sound UNSAT, None = host
    verifies the returned assignment or falls back to CDCL)."""

    def available_for(self, ctx) -> bool:
        # only the cheap forced-off check; check_assumption_sets gates
        # on the backend and the device verdict first thing
        return pallas_enabled() is not False

    def check_assumption_sets(
        self, ctx, assumption_sets: List[List[int]], search: bool = True
    ) -> Optional[Tuple[List[Optional[bool]], np.ndarray]]:
        """None when no dense layout fits the caps (the caller falls
        through to the gather backend).

        Two layouts compete per dispatch, picked by estimated streamed
        cells:

        - **union**: one [C, V] pool over the union cone, all lanes
          sweep it together — wins when lanes share most of their cone
          (sibling forks of one path);
        - **per-lane batched**: each lane remapped into its own compact
          space, planes [B, C_max, V_max], batched matmuls — wins when
          cones are mostly disjoint (frontiers spanning functions or
          contracts), where the union matrix is block-diagonal zeros.

        ``search=False`` disables the DPLL decision stack (BCP-only
        sweeps, sound UNSAT detection still on); it is also disabled
        automatically for cones past the stack budget."""
        if not _use_pallas():
            return None  # demoted device / CPU backend not forced
        if not assumption_sets:
            return [], np.zeros((0, ctx.solver.num_vars + 1), np.int8)
        # Per-lane cones go through the cross-dispatch cone memo:
        # sibling batches repeat assumption sets, so an unchanged pool
        # serves them without re-walking the CSR store.
        from mythril_tpu.ops.incremental import get_cone_memo

        memo = get_cone_memo()
        lane_cones = [memo.cone(ctx, lits) for lits in assumption_sets]
        batch = len(assumption_sets)
        union_ci = np.unique(np.concatenate(
            [ci for ci, _ in lane_cones]
        )) if lane_cones else np.empty(0, np.int64)
        union_cv = np.unique(np.concatenate(
            [cv for _, cv in lane_cones]
        )) if lane_cones else np.empty(0, np.int64)
        union_C = _bucket(max(1, len(union_ci)))
        union_V = _bucket(len(union_cv) + 2)
        # Resident-solver unification (the last PR-8 remainder): when
        # the persistent kernel is on and the union cone fits the
        # cone-gather caps, the dense tier DECLINES so the dispatch
        # routes through the gather/cone rows path into the resident
        # kernel — both ladders enter it through ONE state layout
        # (frontier fields + shared extra pool) instead of the dense
        # tier keeping its own host-driven round loop.  Sound: the
        # rows path drops clauses wider than the width cap, which
        # weakens BCP but never verdicts (UNSAT stays a subset
        # refutation, SAT candidates are host-verified).  Oversized
        # cones keep the dense Pallas ladder — it has no width cap and
        # its [C, V] incidence layout is the only one that fits them.
        if search:
            from mythril_tpu.ops.batched_sat import (
                MAX_CONE_GATHER_CLAUSES, MAX_CONE_GATHER_VARS,
                dispatch_stats,
            )
            from mythril_tpu.ops.resident import resident_kernel_enabled

            if (
                resident_kernel_enabled()
                and 0 < len(union_ci) <= MAX_CONE_GATHER_CLAUSES
                and len(union_cv) <= MAX_CONE_GATHER_VARS
            ):
                dispatch_stats.resident_delegations += 1
                return None
        max_C = _bucket(max(1, max(len(ci) for ci, _ in lane_cones)))
        max_V = _bucket(2 + max(len(cv) for _, cv in lane_cones))
        B_bucket = max(8, _bucket(batch, floor=8))

        union_chunks = -(-batch // max(
            1, min(MAX_LANES, MAX_LANE_CELLS // union_V)
        ))
        est_union = union_C * union_V * union_chunks
        est_batched = B_bucket * max_C * max_V
        union_ok = DenseClausePool.fits(
            len(union_ci), len(union_cv) + 1, tpu=True
        )
        batched_ok = DenseClausePool.fits_lane(
            max_C, max_V, tpu=True
        )
        if not union_ok and not batched_ok:
            log.debug(
                "no dense layout fits (union %dx%d, per-lane %dx%d)",
                union_C, union_V, max_C, max_V,
            )
            return None  # caller falls through to the gather backend

        from mythril_tpu.ops.device_health import backend_name

        interpret = backend_name() == "cpu"
        if interpret:
            # only a real TPU chews through the large tier; interpret
            # mode (tests) keeps the small caps
            union_ok = union_ok and DenseClausePool.fits(
                len(union_ci), len(union_cv) + 1, tpu=False
            )
            batched_ok = batched_ok and DenseClausePool.fits_lane(
                max_C, max_V, tpu=False
            )
            if not union_ok and not batched_ok:
                return None

        use_batched = batched_ok and (
            not union_ok or est_batched < est_union
        )
        if use_batched:
            statuses, assignments = self._solve_batched(
                ctx, assumption_sets, lane_cones, max_C, max_V,
                interpret, search,
            )
        else:
            statuses, assignments = self._solve_union(
                ctx, assumption_sets, union_ci, union_cv, interpret,
                search,
            )
        from mythril_tpu.resilience import faults

        statuses, assignments = faults.maybe_corrupt_lanes(
            statuses, assignments
        )
        results: List[Optional[bool]] = [
            False if statuses[i] == 2 else None for i in range(batch)
        ]
        return results, assignments

    def _solve_union(
        self, ctx, assumption_sets, clause_idx, cone_vars, interpret,
        search,
    ):
        """Union-cone layout: one shared [C, V] incidence pool, solved
        through the round ladder (budgeted rounds, straggler-aware lane
        retirement and bucket re-packing) with tiered hot/cold sweeps:
        hot rows — narrow clauses plus rows touched by the assumption
        frontier, grown with each round's trail — are packed to the row
        prefix and swept every step; the cold remainder joins every
        TIER_PERIOD-th sweep as the conflict/completeness check."""
        from mythril_tpu.ops.batched_sat import dispatch_stats

        # every assumption var is a cone root, so the remap is exactly
        # anchor + cone vars: cone_vars[i] (sorted) -> column i + 2
        num_cone_vars = len(cone_vars) + 1
        batch = len(assumption_sets)
        orig_v1 = ctx.solver.num_vars + 1
        assignments = np.zeros((batch, orig_v1), dtype=np.int8)
        assignments[:, 1] = 1

        # union remap through the cone memo: the dedupe/remap pass over
        # a ~10k-clause union cone is pure host CPU, and sibling
        # frontier batches present the same union while the pool holds
        # still.  Hit-or-miss, the returned arrays are never mutated —
        # the hot-tier growth below permutes COPIES into its layout.
        import zlib

        from mythril_tpu.ops.incremental import get_cone_memo

        digest = (int(clause_idx.size),
                  zlib.crc32(clause_idx.tobytes()))
        urow, ulit, width_arr = get_cone_memo().get_or_build(
            ctx, ("union_remap", digest),
            lambda: remap_cone_csr(ctx, clause_idx, cone_vars),
        )
        n_rows = len(clause_idx)
        seed_lists = [
            np.abs(assumption_columns(cone_vars, lits))
            for lits in assumption_sets if lits
        ]
        seed_cols = (
            np.unique(np.concatenate(seed_lists))
            if seed_lists else np.empty(0, np.int64)
        )
        C = _bucket(max(1, n_rows))
        V = _bucket(num_cone_vars + 1)
        TC = _tile_c(C, V)
        tier_period = _tier_period()
        tier_on = tier_period > 1
        # the initial hot candidates (narrow clauses + rows touched by
        # the assumption frontier) are recorded but the FIRST round
        # always sweeps the full cone: the first trail is what tells us
        # which part of the circuit the search actually exercises, and
        # a hot tier seeded from assumptions alone starves completion
        # (measured on the 16-bit MUL circuits: blind decisions on
        # cold-only vars churn conflicts for the whole budget)
        hot_mask = (
            _hot_row_mask(urow, ulit, width_arr, seed_cols)
            if tier_on else np.zeros(len(width_arr), dtype=bool)
        )
        hot_c = 0  # engaged by grow_hot once a trail exists
        pool = DenseClausePool()
        pool.refresh_coords(urow, ulit, width_arr, n_rows, num_cone_vars)
        inverse = np.zeros(pool.V, dtype=np.int64)
        inverse[1] = 1
        inverse[2 : 2 + len(cone_vars)] = cone_vars

        V = pool.V
        statuses = np.zeros(batch, dtype=np.int32)
        chunk_lanes = max(8, min(MAX_LANES, MAX_LANE_CELLS // V))
        steps = DPLL_STEPS_INTERPRET if interpret else DPLL_STEPS
        search_ceiling = (
            DPLL_MAX_VARS_INTERPRET if interpret else DPLL_MAX_VARS
        )
        decisions = MAX_DECISIONS if (search and V <= search_ceiling) else 0
        # warm start: phases of the newest tagged SAT model, remapped
        # onto the union-cone columns (cone_vars[i] -> column i + 2).
        # Decision bias only, so BCP-only dispatches skip the work.
        from mythril_tpu.ops.batched_sat import warm_pref_row

        pref_row = (
            warm_pref_row(ctx, V, cone_vars=cone_vars, offset=2,
                          lanes=batch, dtype=np.float32)
            if decisions else None
        )

        def round_fn(Bc, round_budget, hot_rows):
            return make_dense_rounds(
                pool.C, V, Bc, round_budget, interpret, decisions,
                hot_rows, tier_period,
            )

        # initially-assigned columns across the chunk (anchor, bucket
        # padding, any lane's assumptions): everything a survivor
        # assigns beyond these is the round's trail
        for start in range(0, batch, chunk_lanes):
            chunk = assumption_sets[start : start + chunk_lanes]
            n = len(chunk)
            B = max(8, _bucket(n, floor=8))
            A0 = np.zeros((B, V), dtype=np.float32)
            A0[:, 1] = 1.0  # constant-TRUE anchor
            # bucket-padding columns occur in no clause; preassign them
            # so the DPLL never spends decisions completing them
            A0[:, num_cone_vars + 1:] = 1.0
            # pad lanes likewise fully assigned (and retired from step
            # 0 via the ladder's pad status)
            A0[n:, :] = 1.0
            for lane, lits in enumerate(chunk):
                cols = assumption_columns(cone_vars, lits)
                A0[lane, np.abs(cols)] = np.where(cols > 0, 1.0, -1.0)
            seeded = np.any(A0[:n] != 0.0, axis=0)
            # layout state the trail growth mutates (carried across
            # chunks so a grown tier serves the rest of the batch).
            # ``rowmap`` tracks original→current row ids so the shared
            # literal→row adjacency index (built ONCE per union
            # layout, ops/frontier.py) keeps serving after hot-first
            # permutations; ``seen`` is the cross-round frontier — only
            # columns newly assigned since the last round pay an
            # adjacency lookup, instead of an O(nnz) isin scan of the
            # whole coordinate list every round
            from mythril_tpu.ops.frontier import (
                LitAdjacency, frontier_enabled,
            )

            layout = {"urow": urow, "width": width_arr, "hot": hot_mask,
                      "rowmap": np.arange(len(width_arr), dtype=np.int64),
                      "seen": seeded.copy()}
            adj_index = (
                LitAdjacency(urow, ulit, len(width_arr))
                if (tier_on and frontier_enabled() and len(ulit))
                else None
            )

            def grow_hot(live_A, hot_cur):
                """Fold the round trail (columns newly assigned by any
                survivor) into the hot tier — the tier ENGAGES here
                after the first round's full-cone sweeps showed which
                rows the search exercises — rebuilding the hot-first
                layout only when the hot bucket actually grows."""
                if not len(ulit):
                    return None
                mask = layout["hot"]
                if adj_index is not None:
                    # adjacency-gather frontier: rows adjacent to the
                    # columns assigned since the LAST round only
                    fresh = np.nonzero(
                        np.any(np.abs(live_A) > 0.5, axis=0)
                        & ~layout["seen"]
                    )[0]
                    if fresh.size:
                        layout["seen"] = layout["seen"].copy()
                        layout["seen"][fresh] = True
                        touched = adj_index.rows_for_vars(fresh)
                        if touched.size:
                            mask = mask.copy()
                            mask[layout["rowmap"][touched]] = True
                            layout["hot"] = mask
                else:
                    trail = np.nonzero(
                        np.any(np.abs(live_A) > 0.5, axis=0) & ~seeded
                    )[0]
                    if trail.size:
                        hit = np.isin(
                            np.abs(ulit.astype(np.int64)), trail
                        )
                        mask = mask.copy()
                        mask[np.unique(layout["urow"][hit])] = True
                        layout["hot"] = mask
                new_hot_c = _bucket(max(1, int(mask.sum())), floor=TC)
                if new_hot_c <= hot_cur or new_hot_c * 2 > C:
                    return None
                order2, new_pos2 = _hot_first_perm(mask)
                layout["urow"] = new_pos2[layout["urow"]]
                layout["width"] = layout["width"][order2]
                layout["hot"] = mask[order2]
                layout["rowmap"] = new_pos2[layout["rowmap"]]
                pool.refresh_coords(
                    layout["urow"], ulit, layout["width"], n_rows,
                    num_cone_vars,
                )
                return (pool.P, pool.N, pool.width), new_hot_c

            st_out, A_host = _run_dense_ladder(
                round_fn, (pool.P, pool.N, pool.width), A0,
                n, decisions, steps, interpret,
                hot_c=hot_c, lane_floor=8,
                grow_hot=grow_hot if tier_on else None,
                pref_row=pref_row,
            )
            # trail growth may have reordered rows for the next chunk;
            # refresh the chunk-level views
            urow, width_arr, hot_mask = (
                layout["urow"], layout["width"], layout["hot"]
            )
            dispatch_stats.lane_slots_filled += n
            dispatch_stats.lane_slots_total += B
            statuses[start : start + n] = st_out
            # map cone columns back to original variable ids
            signs = np.sign(A_host).astype(np.int8)  # [n, V]
            for lane in range(n):
                assignments[start + lane, inverse[1:num_cone_vars + 1]] = (
                    signs[lane, 1 : num_cone_vars + 1]
                )
        return statuses, assignments

    def _solve_batched(
        self, ctx, assumption_sets, lane_cones, max_C, max_V, interpret,
        search,
    ):
        """Per-lane-cone layout: [B, C, V] planes, batched matmuls,
        driven through the round ladder (lane retirement compacts the
        per-lane planes too, so a straggler stops streaming its retired
        siblings' incidence data).  No tier split here: hot tiers need
        the trail-growth feedback loop (union layout), and a static
        assumption-seeded tier measurably starves completion."""
        from mythril_tpu.ops.batched_sat import dispatch_stats

        batch = len(assumption_sets)
        orig_v1 = ctx.solver.num_vars + 1
        assignments = np.zeros((batch, orig_v1), dtype=np.int8)
        assignments[:, 1] = 1
        statuses = np.zeros(batch, dtype=np.int32)

        cells = max_C * max_V
        budget_cells = 2 * (
            MAX_CELLS_DENSE if interpret else MAX_CELLS_DENSE_TPU
        )
        lanes_budget = max(1, budget_cells // cells)
        # floor to a power of two so the bucketed B never exceeds the
        # budget the chunk was sized for
        chunk_lanes = 1
        while chunk_lanes * 2 <= min(MAX_LANES, lanes_budget):
            chunk_lanes *= 2
        steps = DPLL_STEPS_INTERPRET if interpret else DPLL_STEPS
        search_ceiling = (
            DPLL_MAX_VARS_INTERPRET if interpret else DPLL_MAX_VARS
        )
        decisions = (
            MAX_DECISIONS if (search and max_V <= search_ceiling) else 0
        )
        from mythril_tpu.ops.batched_sat import warm_pref_row
        from mythril_tpu.ops.incremental import get_cone_memo

        memo = get_cone_memo()

        for start in range(0, batch, chunk_lanes):
            chunk = assumption_sets[start : start + chunk_lanes]
            chunk_cones = lane_cones[start : start + chunk_lanes]
            B = _bucket(len(chunk), floor=min(8, chunk_lanes))
            lane_floor = min(8, chunk_lanes)
            A0 = np.zeros((B, max_V), dtype=np.float32)
            A0[:, 1] = 1.0
            A0[len(chunk):, :] = 1.0  # pad lanes fully assigned
            width = np.zeros((B, max_C), dtype=np.float32)
            pref_plane = np.zeros((B, max_V), dtype=np.float32)
            pref_seeded = False
            pos_l, pos_r, pos_c = [], [], []
            neg_l, neg_r, neg_c = [], [], []
            inverses = []
            for lane, (lits, (ci, cv)) in enumerate(
                zip(chunk, chunk_cones)
            ):
                inverse = np.zeros(len(cv) + 2, dtype=np.int64)
                inverse[1] = 1
                inverse[2:] = cv
                inverses.append(inverse)
                A0[lane, len(cv) + 2:] = 1.0  # per-lane padding cols
                if decisions:
                    row = warm_pref_row(
                        ctx, max_V, cone_vars=cv, offset=2, lanes=1,
                        dtype=np.float32,
                    )
                    if row is not None:
                        pref_plane[lane] = row
                        pref_seeded = True
                # per-lane remap through the cone memo (sibling batches
                # repeat assumption sets against an unchanged pool)
                urow, ulit, width_arr = memo.get_or_build(
                    ctx, ("lane_remap", tuple(sorted(lits))),
                    lambda ci=ci, cv=cv: remap_cone_csr(ctx, ci, cv),
                )
                width[lane, : len(ci)] = width_arr
                pos = ulit > 0
                pos_l.append(np.full(int(pos.sum()), lane, dtype=np.int64))
                pos_r.append(urow[pos])
                pos_c.append(ulit[pos])
                neg_l.append(np.full(int((~pos).sum()), lane, dtype=np.int64))
                neg_r.append(urow[~pos])
                neg_c.append(-ulit[~pos])
                cols = assumption_columns(cv, lits)
                A0[lane, np.abs(cols)] = np.where(cols > 0, 1.0, -1.0)
            pos_l, pos_r, pos_c, neg_l, neg_r, neg_c = (
                np.concatenate(part) if part else np.empty(0, np.int64)
                for part in (pos_l, pos_r, pos_c, neg_l, neg_r, neg_c)
            )
            from mythril_tpu.ops.device_placement import place

            build = _make_lane_incidence_builder(
                B, max_C, max_V,
                _bucket(max(1, len(pos_l)), floor=256),
                _bucket(max(1, len(neg_l)), floor=256),
            )
            # h2d: (lane, row, col) coordinate triples + the width plane
            from mythril_tpu.ops.batched_sat import dispatch_stats as _ds

            _ds.h2d_bytes += (
                4 * 3 * (build.n_pos + build.n_neg) + int(width.nbytes)
            )
            P, N, W = build(
                place(_pad_coords(pos_l, build.n_pos)),
                place(_pad_coords(pos_r, build.n_pos)),
                place(_pad_coords(pos_c, build.n_pos)),
                place(_pad_coords(neg_l, build.n_neg)),
                place(_pad_coords(neg_r, build.n_neg)),
                place(_pad_coords(neg_c, build.n_neg)),
                place(width),
            )
            def round_fn(Bc, round_budget, hot_rows):
                return make_batched_rounds(
                    max_C, max_V, Bc, round_budget, decisions,
                )

            def compact_planes(planes, idx):
                import jax.numpy as jnp

                j = jnp.asarray(idx)
                return tuple(jnp.take(p, j, axis=0) for p in planes)

            n = len(chunk)
            st_out, A_host = _run_dense_ladder(
                round_fn, (P, N, W), A0, n, decisions, steps, interpret,
                lane_floor=lane_floor, compact_planes=compact_planes,
                pref_row=pref_plane if pref_seeded else None,
            )
            dispatch_stats.lane_slots_filled += n
            dispatch_stats.lane_slots_total += B
            statuses[start : start + n] = st_out
            signs = np.sign(A_host).astype(np.int8)
            for lane in range(n):
                inverse = inverses[lane]
                ncols = len(inverse) - 1
                assignments[start + lane, inverse[1:]] = (
                    signs[lane, 1 : ncols + 1]
                )
        return statuses, assignments


_pallas_backend: Optional[PallasSatBackend] = None


def get_pallas_backend() -> PallasSatBackend:
    global _pallas_backend
    if _pallas_backend is None:
        _pallas_backend = PallasSatBackend()
    return _pallas_backend
